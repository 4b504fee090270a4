"""plane.wait_ms: time per step the loop waited on the data plane, the
program's ``plane.next_wait`` span (``data/pipeline.py``), summed over
the window."""


def read(ctx):
    h = ctx.hook
    if h.plane_wait0 is None or h.plane_wait1 is None or not ctx.steps:
        return None
    return 1e3 * (h.plane_wait1 - h.plane_wait0) / ctx.steps
