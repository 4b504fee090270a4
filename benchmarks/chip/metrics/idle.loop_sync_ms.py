"""idle.loop_sync_ms: device-idle ms per traced step under the program's
``loop.sync`` span (``api/loop.py``): the chip waits while the host reads
the step's scalars (``chipbench/spans.py``)."""
from chipbench import spans


def read(ctx):
    return spans.idle_ms(ctx, "loop.sync")
