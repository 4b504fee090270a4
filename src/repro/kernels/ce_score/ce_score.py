"""Fused cross-entropy + importance-score Pallas TPU kernel.

The scoring pass of the paper (Algorithm 1 line 7) needs, per token,
three vocab reductions: logsumexp(z), logsumexp(2z), and z_y. A naive
implementation round-trips the (tokens × V) softmax gradient through HBM
(V up to 262k). This kernel streams vocab tiles HBM→VMEM once, keeping
four (tokens_tile, 1) running accumulators in VMEM scratch — the classic
online-softmax trick applied to BOTH moments simultaneously, fused with the
label gather.

Grid: (T/bt, V/bv) — the vocab axis is the minor (sequential) grid dim, so
scratch persists across it. Tiles are MXU/VPU aligned (bt×bv multiples of
8×128). Everything accumulates in f32 regardless of input dtype.

Layout: per-token vectors (labels, outputs, accumulators) travel as
(tokens, 1) columns — the shape the vocab reductions produce with
``keepdims`` — and per-row values of the blocked variant as (rows, 1, 1).
Mosaic refuses rank-1 blocks that are neither the whole array nor
lane-aligned, so nothing here is rank 1; the survival mask rides in SMEM
as one int per row block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
# elements of one logits tile: (128, 2048) is what fits the chip's default
# scoped VMEM with the f32 temporaries of the update; the blocked variant
# narrows its vocab tile to stay within it
TILE_ELEMS = 128 * 2048


def _kernel(z_ref, labels_ref, ce_ref, g2_ref,
            m1_ref, s1_ref, m2_ref, s2_ref, zy_ref, *, bv, n_v, V):
    v_idx = pl.program_id(1)

    @pl.when(v_idx == 0)
    def _init():
        m1_ref[...] = jnp.full_like(m1_ref, NEG)
        s1_ref[...] = jnp.zeros_like(s1_ref)
        m2_ref[...] = jnp.full_like(m2_ref, NEG)
        s2_ref[...] = jnp.zeros_like(s2_ref)
        zy_ref[...] = jnp.zeros_like(zy_ref)

    # the last vocab tile may run past V: its tail lanes are masked to NEG
    # (no softmax mass) instead of padding the logits in HBM
    cols = v_idx * bv + jax.lax.broadcasted_iota(jnp.int32, z_ref.shape, 1)
    z = jnp.where(cols < V, z_ref[...].astype(jnp.float32), NEG)  # (bt, bv)
    labels = labels_ref[...]                           # (bt, 1)

    # streaming logsumexp of z
    m1 = m1_ref[...]
    mt = jnp.max(z, axis=-1, keepdims=True)
    m1n = jnp.maximum(m1, mt)
    s1_ref[...] = s1_ref[...] * jnp.exp(m1 - m1n) + \
        jnp.sum(jnp.exp(z - m1n), axis=-1, keepdims=True)
    m1_ref[...] = m1n

    # streaming logsumexp of 2z
    z2 = 2.0 * z
    m2 = m2_ref[...]
    mt2 = jnp.max(z2, axis=-1, keepdims=True)
    m2n = jnp.maximum(m2, mt2)
    s2_ref[...] = s2_ref[...] * jnp.exp(m2 - m2n) + \
        jnp.sum(jnp.exp(z2 - m2n), axis=-1, keepdims=True)
    m2_ref[...] = m2n

    # fused label gather: exactly one column matches across all tiles
    zy_ref[...] += jnp.sum(jnp.where(cols == labels, z, 0.0), axis=-1,
                           keepdims=True)

    @pl.when(v_idx == n_v - 1)
    def _finalize():
        lse = m1_ref[...] + jnp.log(s1_ref[...])
        lse2 = m2_ref[...] + jnp.log(jnp.maximum(s2_ref[...], 1e-30))
        zy = zy_ref[...]
        ce_ref[...] = lse - zy
        g2 = jnp.exp(lse2 - 2.0 * lse) - 2.0 * jnp.exp(zy - lse) + 1.0
        g2_ref[...] = jnp.maximum(g2, 0.0)


def _block_kernel(live_ref, z_ref, labels_ref, ce_ref, g2_ref,
                  m1_ref, s1_ref, m2_ref, s2_ref, zy_ref, *, bv, n_v, V):
    """Row-blocked, survival-gated variant of ``_kernel``: grid
    (B/bb, Tc/bt, V/bv), vocab minor so the (bb, bt, 1) scratch streams
    the same online-softmax recurrence per token — but every (bb, bt)
    tile whose row block is fully dead is SKIPPED outright (no scratch
    init, no update, no finalize). Outputs are per-ROW masked sums (not
    per-token stats): ce/g2 accumulate across the t grid dim into a
    revisited (bb, 1, 1) output block."""
    t_idx = pl.program_id(1)
    v_idx = pl.program_id(2)

    # the output block is revisited across (t, v): zero it exactly once,
    # on first visit — unconditionally, dead blocks included, so a
    # fully-pruned row block reads back as 0.0 rather than garbage
    @pl.when((t_idx == 0) & (v_idx == 0))
    def _zero():
        ce_ref[...] = jnp.zeros_like(ce_ref)
        g2_ref[...] = jnp.zeros_like(g2_ref)

    # the survival gate: one predicate for the whole tile. liveness is
    # constant across t/v within a call, so init/update/finalize agree.
    any_alive = live_ref[pl.program_id(0)] > 0

    @pl.when(any_alive & (v_idx == 0))
    def _init():
        m1_ref[...] = jnp.full_like(m1_ref, NEG)
        s1_ref[...] = jnp.zeros_like(s1_ref)
        m2_ref[...] = jnp.full_like(m2_ref, NEG)
        s2_ref[...] = jnp.zeros_like(s2_ref)
        zy_ref[...] = jnp.zeros_like(zy_ref)

    @pl.when(any_alive)
    def _update():
        cols = v_idx * bv + jax.lax.broadcasted_iota(jnp.int32,
                                                     z_ref.shape, 2)
        z = jnp.where(cols < V, z_ref[...].astype(jnp.float32), NEG)
        labels = labels_ref[...]                       # (bb, bt, 1)

        m1 = m1_ref[...]
        mt = jnp.max(z, axis=-1, keepdims=True)
        m1n = jnp.maximum(m1, mt)
        s1_ref[...] = s1_ref[...] * jnp.exp(m1 - m1n) + \
            jnp.sum(jnp.exp(z - m1n), axis=-1, keepdims=True)
        m1_ref[...] = m1n

        z2 = 2.0 * z
        m2 = m2_ref[...]
        mt2 = jnp.max(z2, axis=-1, keepdims=True)
        m2n = jnp.maximum(m2, mt2)
        s2_ref[...] = s2_ref[...] * jnp.exp(m2 - m2n) + \
            jnp.sum(jnp.exp(z2 - m2n), axis=-1, keepdims=True)
        m2_ref[...] = m2n

        # label gather; labels < 0 (unsupervised/pad) match no column
        zy_ref[...] += jnp.sum(jnp.where(cols == labels, z, 0.0), axis=-1,
                               keepdims=True)

    @pl.when(any_alive & (v_idx == n_v - 1))
    def _finalize():
        lse = m1_ref[...] + jnp.log(s1_ref[...])
        lse2 = m2_ref[...] + jnp.log(jnp.maximum(s2_ref[...], 1e-30))
        zy = zy_ref[...]
        mask = (labels_ref[...] >= 0).astype(jnp.float32)
        ce = (lse - zy) * mask
        g2 = jnp.exp(lse2 - 2.0 * lse) - 2.0 * jnp.exp(zy - lse) + 1.0
        g2 = jnp.maximum(g2, 0.0) * mask
        ce_ref[...] += jnp.sum(ce, axis=1, keepdims=True)
        g2_ref[...] += jnp.sum(g2, axis=1, keepdims=True)


def ce_score_block_pallas(logits, labels, alive, *, block_b=8, block_t=128,
                          block_v=2048, interpret=False):
    """Survival-gated row-blocked CE+score chunk: logits (B, Tc, V),
    labels (B, Tc) int32 (< 0 = unsupervised, masked out of the sums),
    alive (B,) f32 survival mask → (ce_sum, g2_sum) f32 (B,), the MASKED
    per-row sums over this time chunk. Row blocks whose alive lanes are
    all zero skip every (bb, bt, bv) tile (their rows return 0.0).

    Ragged shapes: the vocab tail is masked inside the kernel; time/batch
    rows pad with label −1 (masked), alive with 0 (pad row blocks skip)."""
    B, Tc, V = logits.shape
    bb = min(block_b, B)
    bt = min(block_t, Tc)
    bv = min(block_v, V, max(128, TILE_ELEMS // (bb * bt) // 128 * 128))
    Bp = -(-B // bb) * bb
    Tp = -(-Tc // bt) * bt
    if (Bp, Tp) != (B, Tc):
        logits = jnp.pad(logits, ((0, Bp - B), (0, Tp - Tc), (0, 0)),
                         constant_values=NEG)
        labels = jnp.pad(labels, ((0, Bp - B), (0, Tp - Tc)),
                         constant_values=-1)
        alive = jnp.pad(alive, (0, Bp - B))
    n_v = -(-V // bv)
    live = (jnp.max(alive.reshape(Bp // bb, bb), axis=1) > 0.0) \
        .astype(jnp.int32)

    kernel = functools.partial(_block_kernel, bv=bv, n_v=n_v, V=V)
    ce, g2 = pl.pallas_call(
        kernel,
        grid=(Bp // bb, Tp // bt, n_v),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),       # row-block liveness
            pl.BlockSpec((bb, bt, bv), lambda i, t, v: (i, t, v)),
            pl.BlockSpec((bb, bt, 1), lambda i, t, v: (i, t, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, 1, 1), lambda i, t, v: (i, 0, 0)),
            pl.BlockSpec((bb, 1, 1), lambda i, t, v: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((Bp, 1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bb, bt, 1), jnp.float32),   # m1
            pltpu.VMEM((bb, bt, 1), jnp.float32),   # s1
            pltpu.VMEM((bb, bt, 1), jnp.float32),   # m2
            pltpu.VMEM((bb, bt, 1), jnp.float32),   # s2
            pltpu.VMEM((bb, bt, 1), jnp.float32),   # zy
        ],
        interpret=interpret,
    )(live, logits, labels.astype(jnp.int32)[..., None])
    return ce[:B, 0, 0], g2[:B, 0, 0]


def ce_score_pallas(logits, labels, *, block_t=128, block_v=2048,
                    interpret=False):
    """logits: (T, V); labels: (T,) int32 → (ce, gnorm2) f32 (T,)."""
    T, V = logits.shape
    bt = min(block_t, T)
    bv = min(block_v, V)
    # pad tokens to the tile (padded rows inert); the vocab tail is masked
    # inside the kernel
    Tp = -(-T // bt) * bt
    if Tp != T:
        logits = jnp.pad(logits, ((0, Tp - T), (0, 0)), constant_values=NEG)
        labels = jnp.pad(labels, (0, Tp - T))
    n_v = -(-V // bv)

    kernel = functools.partial(_kernel, bv=bv, n_v=n_v, V=V)
    ce, g2 = pl.pallas_call(
        kernel,
        grid=(Tp // bt, n_v),
        in_specs=[
            pl.BlockSpec((bt, bv), lambda t, v: (t, v)),
            pl.BlockSpec((bt, 1), lambda t, v: (t, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bt, 1), lambda t, v: (t, 0)),
            pl.BlockSpec((bt, 1), lambda t, v: (t, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Tp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Tp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bt, 1), jnp.float32),   # m1
            pltpu.VMEM((bt, 1), jnp.float32),   # s1
            pltpu.VMEM((bt, 1), jnp.float32),   # m2
            pltpu.VMEM((bt, 1), jnp.float32),   # s2
            pltpu.VMEM((bt, 1), jnp.float32),   # zy
        ],
        interpret=interpret,
    )(logits, labels[:, None])
    return ce[:T, 0], g2[:T, 0]
