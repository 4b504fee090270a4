"""loop.hooks_ms: host ms per traced step in the program's
``loop.emit.*`` spans (``api/loop.py``: the observer hooks of every loop
event), clipped to the window (``chipbench/spans.py``)."""
from chipbench import spans


def read(ctx):
    return spans.host_ms(ctx, "loop.emit.*")
