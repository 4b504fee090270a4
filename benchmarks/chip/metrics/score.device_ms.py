"""score.device_ms: device time per step of pool scoring and the
on-device gather of the selected rows (``scoring/engine.py``: the
survival-pruned score program ``jit_pruned`` and the row take
``jit__lambda``)."""

PROGRAMS = ("jit_pruned", "jit__lambda")


def read(ctx):
    s = sum(v for k, v in ctx.trace["modules"].items() if k in PROGRAMS)
    return 1e3 * s / ctx.traced_steps if s > 0 and ctx.traced_steps else None
