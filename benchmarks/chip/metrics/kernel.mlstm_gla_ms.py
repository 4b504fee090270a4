"""kernel.mlstm_gla_ms: device time per step of the mLSTM's gated linear
attention kernels, in every program: the operations whose names start
with ``mlstm_gla_`` (the Pallas calls ``mlstm_gla_fwd``,
``mlstm_gla_fwd_res`` and ``mlstm_gla_bwd``). Where the mixing does not
run as the kernels, there is nothing to read."""

PREFIX = "mlstm_gla_"


def read(ctx):
    s = sum(v for k, v in ctx.trace["ops"].items() if k.startswith(PREFIX))
    return 1e3 * s / ctx.traced_steps if s > 0 and ctx.traced_steps else None
