"""mfu: the whole step's share of the chip's bf16 peak — model FLOPs per
step (``chipbench/flops.py``) times the steps of the traced part of the
window, over its length and the peak (``chipbench/peaks.json``)."""
from chipbench import flops


def read(ctx):
    if not ctx.traced_steps or ctx.trace["window_s"] <= 0:
        return None
    rate = flops.step_flops(ctx.cfg, ctx.traffic) * ctx.traced_steps \
        / ctx.trace["window_s"]
    return 100.0 * rate / flops.peak(ctx.device_kind)
