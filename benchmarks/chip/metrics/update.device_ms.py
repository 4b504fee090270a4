"""update.device_ms: device time per step of the train-step program
(``core/is_train.py``'s step, jitted by ``Experiment``)."""

PROGRAMS = ("jit_host_step",)


def read(ctx):
    s = sum(v for k, v in ctx.trace["modules"].items() if k in PROGRAMS)
    return 1e3 * s / ctx.traced_steps if s > 0 and ctx.traced_steps else None
