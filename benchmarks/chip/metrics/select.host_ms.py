"""select.host_ms: host ms per traced step in the program's
``sampler.select`` span (``sampler/schemes.py``: the race, the score
store's update and the tau EMA), clipped to the window
(``chipbench/spans.py``)."""
from chipbench import spans


def read(ctx):
    return spans.host_ms(ctx, "sampler.select")
