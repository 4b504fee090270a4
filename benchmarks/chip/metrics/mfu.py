"""mfu: the whole step's share of the peak of the cell's chips — model
FLOPs per step (``chipbench/flops.py``, every row of the global batch and
pool) times the steps of the traced part of the window, over its length
and the chips' bf16 peak, ``ctx.chips`` times one chip's
(``chipbench/peaks.json``)."""
from chipbench import flops


def read(ctx):
    if not ctx.traced_steps or ctx.trace["window_s"] <= 0:
        return None
    rate = flops.step_flops(ctx.cfg, ctx.traffic) * ctx.traced_steps \
        / ctx.trace["window_s"]
    return 100.0 * rate / (ctx.chips * flops.peak(ctx.device_kind))
