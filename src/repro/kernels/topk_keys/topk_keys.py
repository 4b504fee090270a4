"""Fused exponential-race key generation Pallas TPU kernel.

The sharded selection path (``repro.sampler.selection``) turns one
proportional draw into a per-shard hot loop: hash (step, gid) → uniform →
exponential → divide by the smoothed/sharpened proposal probability. A
naive implementation round-trips the shard's score vector through several
elementwise passes; this kernel streams each score tile HBM→VMEM once and
emits the race key ``r_i = −log(u_i) / p_i`` in the same pass — counter
hash, fill/clamp/sharpen and the λ-mixture fused per element.

Grid: (n/bt,), one tile of bt keys per step laid out as (bt/128, 128)
— rank-1 blocks do not lower on TPU — all lanes independent (the partial
top-k over the keys runs as ``lax.top_k`` in the same jit — see
``ops.topk_race_keys``). The integer hash is the same murmur3-finalizer
composition as ``selection.hash_uniform``; uint32 wrap-around is exact on
host and device, the float tail differs from the host's float64 only in
the last ulps.

Layout mirrors ``repro.kernels.ce_score``: kernel here, pure-jnp oracle
in ``ref.py``, jitted public wrapper in ``ops.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

EPS = 1e-12      # selection.EPS — the distribution_from score clamp
LANES = 128      # minor dim of the 2-D layout every per-element kernel uses


def fmix32(x):
    """murmur3's 32-bit finalizer on jnp uint32 (wraps mod 2^32)."""
    x = x.astype(jnp.uint32)
    x ^= x >> jnp.uint32(16)
    x *= jnp.uint32(0x85EBCA6B)
    x ^= x >> jnp.uint32(13)
    x *= jnp.uint32(0xC2B2AE35)
    x ^= x >> jnp.uint32(16)
    return x


def hash_unit(h):
    """uint32 hash → u ∈ (0, 1) from its top 24 bits, the same value as
    ``selection.hash_uniform``. The bits go through int32 (exact below
    2²⁴): Mosaic has no uint32 → float32 cast."""
    top = jax.lax.bitcast_convert_type(h >> jnp.uint32(8), jnp.int32)
    return top.astype(jnp.float32) * jnp.float32(2.0 ** -24) \
        + jnp.float32(2.0 ** -25)


def lane_rows(n, block_t):
    """The (rows, 128) layout of n per-element values: (padded row count,
    rows per block). A block is ``block_t`` elements — a multiple of
    8·128 on TPU — or the whole array when that is smaller."""
    rows = -(-n // LANES)
    br = min(rows, max(block_t // LANES, 1))
    return -(-rows // br) * br, br


def race_keys_math(scores, seen, gids_u32, ctx_u32, fill_pow, scale,
                   lam_over_n, inv_t):
    """The per-element key math, shared verbatim by the kernel body and
    the ``ref.py`` oracle: hash → u ∈ (0,1) → E = −log u, then
    p = (1−λ)·s̃/S̃ + λ/n with s̃ = max(s, EPS)^(1/T) (fill for unseen),
    key = E / p. ``scale`` = (1−λ)/S̃."""
    h = fmix32(gids_u32 * jnp.uint32(0x9E3779B9) ^ ctx_u32)
    u = hash_unit(fmix32(h + jnp.uint32(0x6A09E667)))
    s = scores.astype(jnp.float32)
    # pow via exp·log so host/oracle/kernel share one formulation
    sp = jnp.exp(jnp.log(jnp.maximum(s, EPS)) * inv_t)
    sp = jnp.where(seen > 0, sp, fill_pow)
    p = sp * scale + lam_over_n
    return -jnp.log(u) / p


def _kernel(fp_ref, ctx_ref, gid_ref, s_ref, seen_ref, r_ref):
    fill_pow, scale, lam_over_n, inv_t = (fp_ref[0], fp_ref[1], fp_ref[2],
                                          fp_ref[3])
    r = race_keys_math(s_ref[...], seen_ref[...], gid_ref[...], ctx_ref[0],
                       fill_pow, scale, lam_over_n, inv_t)
    # padded lanes (valid encoded as seen < 0) never win a bottom-k
    r_ref[...] = jnp.where(seen_ref[...] < 0, jnp.float32(jnp.inf), r)


def race_keys_pallas(scores, seen, gids_u32, ctx_u32, fparams, *,
                     block_t=1024, interpret=False):
    """scores/seen: (n,) f32 (seen: 1 seen, 0 unseen, −1 padded lane);
    gids_u32: (n,) uint32; ctx_u32: (1,) uint32; fparams: (4,) f32
    [fill_pow, (1−λ)/S̃, λ/n, 1/T] → race keys (n,) f32 (+inf on pads).
    """
    n = scores.shape[0]
    rows, br = lane_rows(n, block_t)
    npad = rows * LANES - n
    if npad:
        scores = jnp.pad(scores, (0, npad))
        seen = jnp.pad(seen, (0, npad), constant_values=-1.0)
        gids_u32 = jnp.pad(gids_u32, (0, npad))
    tile = pl.BlockSpec((br, LANES), lambda t: (t, 0))
    r = pl.pallas_call(
        _kernel,
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),       # fparams
            pl.BlockSpec(memory_space=pltpu.SMEM),       # ctx
            tile, tile, tile,
        ],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=interpret,
    )(fparams, ctx_u32, *(x.reshape(rows, LANES)
                          for x in (gids_u32, scores, seen)))
    return r.reshape(-1)[:n]
