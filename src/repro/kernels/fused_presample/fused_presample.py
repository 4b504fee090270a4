"""Fused presample Pallas TPU kernels: blockwise row scoring + pool keys.

The fused presample path (``imp.presample_impl="fused"``) keeps the
B = ratio·b candidate pool device-resident: the forward pass and the
``ce_score`` per-token statistics already run on device, so the two host
round-trips left are (1) reducing the per-token ĝ² statistics to the
paper's per-row score ‖Ĝᵢ‖ and (2) generating the selection race keys.
This module fuses both into Pallas stages so the whole
score → key → top-k → gather chain (``ops.fused_presample``) is one
device program and only the b winners ever leave the chip.

Two kernel bodies, mirroring the existing layouts:

* ``row_score_pallas`` — ``ce_score``-style blockwise reduction: each
  grid step streams a (block_b, T) tile of masked per-token ĝ² HBM→VMEM
  once and emits the per-row score ``sᵢ = sqrt(max(Σₜ ĝ²ᵢₜ·maskᵢₜ,
  1e-20))`` — exactly ``LM.sample_stats``'s reduction of the ce_score
  token stats.
* ``pool_keys_pallas`` — ``topk_keys``-style race-key generation over the
  POOL: hash (pool row, ctx) → u → key ``rᵢ = −log(uᵢ)/gᵢ`` with
  ``gᵢ = sᵢ/Σs`` (the paper's normalised ĝ — no smoothing/temperature;
  presample pools are always fresh). ``pool_keys_math`` is shared
  verbatim with the ``ref.py`` oracle; the uint32 hash matches
  ``selection.hash_uniform`` bit-for-bit, the float tail is f32 vs the
  host's f64 (same contract as ``topk_keys``: candidate sets agree, key
  bytes do not).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.topk_keys.topk_keys import (LANES, fmix32, hash_unit,
                                               lane_rows)


def row_score_math(g2, mask):
    """Per-row score from per-token stats: the paper's ‖Ĝᵢ‖ =
    sqrt(Σₜ ĝ²ᵢₜ) over supervised tokens (the same clamp
    ``LM.sample_stats`` applies), as a (rows, 1) column."""
    s = jnp.sum(g2.astype(jnp.float32) * mask.astype(jnp.float32), axis=-1,
                keepdims=True)
    return jnp.sqrt(jnp.maximum(s, 1e-20))


def _score_kernel(g2_ref, mask_ref, s_ref):
    s_ref[...] = row_score_math(g2_ref[...], mask_ref[...])


def row_score_pallas(g2, mask, *, block_b=128, interpret=False):
    """g2: (B, T) f32 per-token ĝ²; mask: (B, T) supervised-token mask →
    (B,) f32 per-row scores. Grid (B/block_b,): one row-block per step,
    the full token axis streamed in the same tile (T is the sequence
    length — small next to the vocab axis ce_score tiles over). Ragged
    B % block_b is zero-padded; pad rows reduce to sqrt(1e-20) and are
    dropped by the caller."""
    B, T = g2.shape
    bb = min(block_b, B)
    npad = -(-B // bb) * bb - B
    if npad:
        g2 = jnp.pad(g2, ((0, npad), (0, 0)))
        mask = jnp.pad(mask, ((0, npad), (0, 0)))
    s = pl.pallas_call(
        _score_kernel,
        grid=((B + npad) // bb,),
        in_specs=[pl.BlockSpec((bb, T), lambda i: (i, 0)),
                  pl.BlockSpec((bb, T), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bb, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B + npad, 1), jnp.float32),
        interpret=interpret,
    )(g2.astype(jnp.float32), mask.astype(jnp.float32))
    return s[:B, 0]


def pool_exponentials(n, ctx_u32):
    """The race key's numerator, known BEFORE scoring: Eᵢ = −log(uᵢ) with
    u from the identical (pool row, ctx) counter hash as
    ``pool_keys_math`` / ``selection.hash_uniform``. The survival-pruned
    scoring pass derives per-row key bounds Eᵢ/ŝᵢ from these while the
    scores are still partial."""
    idx = jnp.arange(n, dtype=jnp.uint32)
    h = fmix32(idx * jnp.uint32(0x9E3779B9) ^ jnp.asarray(ctx_u32, jnp.uint32))
    return -jnp.log(hash_unit(fmix32(h + jnp.uint32(0x6A09E667))))


def pool_keys_math(scores, idx_u32, ctx_u32, inv_total):
    """The per-row key math, shared verbatim by the kernel body and the
    ``ref.py`` oracle: hash (pool row, ctx) → u ∈ (0,1) (identical uint32
    composition to ``selection.hash_uniform``), g = s·(1/Σs), key =
    −log(u)/g. Smaller key = more likely to win the race."""
    h = fmix32(idx_u32 * jnp.uint32(0x9E3779B9) ^ ctx_u32)
    u = hash_unit(fmix32(h + jnp.uint32(0x6A09E667)))
    g = scores.astype(jnp.float32) * inv_total
    return -jnp.log(u) / jnp.maximum(g, jnp.float32(1e-20))


def _keys_kernel(ctx_ref, it_ref, idx_ref, s_ref, r_ref):
    r = pool_keys_math(s_ref[...], idx_ref[...], ctx_ref[0], it_ref[0])
    # padded lanes (score < 0 sentinel) never win the race
    r_ref[...] = jnp.where(s_ref[...] < 0, jnp.float32(jnp.inf), r)


def pool_keys_pallas(scores, ctx_u32, inv_total, *, block_t=1024,
                     interpret=False):
    """scores: (B,) f32 fresh pool scores (≥ 0; pads as −1); ctx_u32: (1,)
    uint32 plan context; inv_total: (1,) f32 = 1/Σs (traced — changes
    every step without recompiling) → race keys (B,) f32, +inf on pads."""
    B = scores.shape[0]
    rows, br = lane_rows(B, block_t)
    npad = rows * LANES - B
    if npad:
        scores = jnp.pad(scores, (0, npad), constant_values=-1.0)
    idx = jnp.arange(rows * LANES, dtype=jnp.uint32).reshape(rows, LANES)
    tile = pl.BlockSpec((br, LANES), lambda t: (t, 0))
    r = pl.pallas_call(
        _keys_kernel,
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),       # ctx
            pl.BlockSpec(memory_space=pltpu.SMEM),       # 1/Σs
            tile, tile,
        ],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=interpret,
    )(ctx_u32, inv_total, idx, scores.reshape(rows, LANES))
    return r.reshape(-1)[:B]
