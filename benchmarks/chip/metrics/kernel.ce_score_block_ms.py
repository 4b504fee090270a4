"""kernel.ce_score_block_ms: device time per step of the
``ce_score_block`` Pallas kernel, the survival-pruned CE tiles: the
custom calls of the pool-scoring program, where it is the only one."""

PROGRAM, OPCODE = "jit_pruned", "custom-call"


def read(ctx):
    s = ctx.trace["by_module"].get(PROGRAM, {}).get(OPCODE, 0.0)
    return 1e3 * s / ctx.traced_steps if s > 0 and ctx.traced_steps else None
