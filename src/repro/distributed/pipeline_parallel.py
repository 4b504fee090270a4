"""GPipe-style pipeline parallelism over the ``pod`` axis.

Stage parameters are sharded over the pipeline axis (stacked dim 0, one
stage per pod); microbatches stream through with ``ppermute`` handoffs.
Forward runs in P + M − 1 ticks (P stages, M microbatches); because
``ppermute`` is linear/differentiable, ``jax.grad`` through this forward
yields the reverse-schedule backward automatically (GPipe with
recomputation when wrapped in ``jax.checkpoint``).

The pod axis defaults to data-parallel in the production mesh; PP is the
alternative configuration for models whose weights don't fit a single pod's
HBM even fully sharded. Validated against sequential execution in
tests/test_distributed.py on a multi-device host platform.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_forward(stage_params, microbatches, apply_stage, axis_name="pod"):
    """Run inside shard_map over ``axis_name``.

    stage_params: this stage's params (leading stage dim already sliced away
        by shard_map: shard over dim 0).
    microbatches: (M, mb, ...) — replicated across stages; stage 0 feeds
        them in, the last stage's outputs are returned (M, mb, ...).
    apply_stage: (params, x) -> y, same x/y shape for all stages.
    """
    n_stage = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    M = microbatches.shape[0]
    ticks = n_stage + M - 1
    fwd = [(i, (i + 1) % n_stage) for i in range(n_stage)]

    x0 = jnp.zeros_like(microbatches[0])
    outs0 = jnp.zeros_like(microbatches)

    def tick(carry, t):
        held, outs = carry
        # stage 0 injects microbatch t (if any); others use what they hold
        inject = microbatches[jnp.clip(t, 0, M - 1)]
        x = jnp.where(stage == 0, jnp.where(t < M, inject, jnp.zeros_like(inject)),
                      held)
        y = apply_stage(x)
        # last stage commits microbatch (t - n_stage + 1) at this tick
        mb_idx = t - (n_stage - 1)
        commit = (stage == n_stage - 1) & (mb_idx >= 0)
        outs = jax.lax.cond(
            commit,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, y, jnp.maximum(mb_idx, 0), 0),
            lambda o: o, outs)
        # hand y to the next stage (wraps to 0; stage 0 ignores the wrap)
        held_next = jax.lax.ppermute(y, axis_name, fwd)
        return (held_next, outs), None

    (_, outs), _ = jax.lax.scan(tick, (x0, outs0), jnp.arange(ticks))
    # every stage computed `outs`, but only the last stage's is real;
    # broadcast it (cheap: one ppermute ring or psum of masked outs)
    mask = (stage == n_stage - 1).astype(outs.dtype)
    return jax.lax.psum(outs * mask, axis_name)


def make_pipelined_stack(mesh, stage_params_stacked, apply_stage, n_micro):
    """jit-ready wrapper: shard stage params over 'pod', batch over 'data'."""
    def fn(params, batch):
        mb = batch.reshape((n_micro, batch.shape[0] // n_micro) + batch.shape[1:])
        out = jax.shard_map(
            lambda p, m: pipeline_forward(
                jax.tree_util.tree_map(lambda a: a[0], p), m,
                apply_stage, "pod"),
            mesh=mesh,
            in_specs=(P("pod"), P(None, "data")),
            out_specs=P(None, "data"),
            check_vma=False,
        )(params, mb)
        return out.reshape(batch.shape)

    return fn
