"""The mLSTM's chunkwise gated linear attention as Pallas TPU kernels.

Grid (rows, heads, chunks): rows and heads are independent, the chunks
run in order ("arbitrary") with the state in VMEM scratch between them.
q, k and v are read as (chunk, hd) blocks of their (b, s, h·hd) layout,
in their own dtype; the per-position vectors (the chunk-local cumulative
log forget gate, the input gate, the normaliser and their cotangents)
as (1, chunk) rows of (b, h, 1, s) arrays.

Forward, per chunk of Q positions with the state (C, n) entering it:

    y_i  = Σ_{j≤i} D_ij (q_i·k_j) i_j v_j + e_i q_iᵀ C
    n̂_i  = Σ_{j≤i} D_ij (q_i·k_j) i_j     + e_i q_i·n
    C ← g C + Σ_j w_j i_j k_j v_jᵀ,   n ← g n + Σ_j w_j i_j k_j

with c the chunk-local inclusive cumulative sum of log f, D_ij =
exp(c_i − c_j), e_i = exp(c_i), w_j = exp(c_Q − c_j), g = exp(c_Q): all
at most 1. The intra-chunk product runs over sub-tiles of SUB rows and
skips the tiles above the diagonal; the diagonal tile is masked before
the exp. The normaliser is a reduction of the same decayed scores against
the input gate, beside y: no operand is hd + 1 wide. With ``save`` the
state entering each chunk is written out for the backward: C in the
products' operand dtype (the backward only reads it as an operand), n in
float32.

Backward: the chunks in reverse order with the state cotangents (dC, dn)
in VMEM. It emits dq, dk, dv, the input gate's cotangent, and per
position q·dq − k·dk, the cotangent of the global cumulative log forget
gate; ``ops.py`` turns that into log f's by a reverse cumulative sum.

Gates, decays, states and every reduction are float32. The products take
their operands in ``mxu`` (bfloat16 on the chip, as XLA's DEFAULT
precision rounds the float32 einsums of ``chunked_gla`` there; float32
in interpret mode) and accumulate in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUB = 128           # rows of an intra-chunk sub-tile: the MXU's width
VMEM_LIMIT = 64 << 20
f32 = jnp.float32


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=f32)


def _dot_nt(a, b):
    """a · bᵀ"""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=f32)


def _dot_tn(a, b):
    """aᵀ · b"""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=f32)


def _last(row):
    """The last entry of a (1, Q) row, as (1, 1)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lane == row.shape[1] - 1, row, 0.0), axis=1,
                   keepdims=True)


def _tiles(q):
    """Sub-tile rows of a chunk of q positions."""
    return SUB if q % SUB == 0 else q


def _decay(cum_c, cum_r, diag):
    """D (rows × cols) from the column and row pieces of the cumulative
    log gate; on a diagonal tile the upper triangle is masked before the
    exp, where c_i − c_j > 0 could overflow."""
    diff = cum_c - cum_r
    if diag:
        n = diff.shape[0]
        ii = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        jj = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
        diff = jnp.where(jj <= ii, diff, -jnp.inf)
    return jnp.exp(diff)


def _fwd_kernel(q_ref, k_ref, v_ref, cum_ref, ig_ref, *refs, save, mxu):
    if save:
        y_ref, n_ref, cs_ref, ns_ref, c_scr, nv_scr = refs
    else:
        y_ref, n_ref, c_scr, nv_scr = refs

    @pl.when(pl.program_id(2) == 0)
    def _():
        c_scr[...] = jnp.zeros_like(c_scr)
        nv_scr[...] = jnp.zeros_like(nv_scr)

    nq = q_ref.shape[0]
    t = _tiles(nq)
    cum, ig = cum_ref[...], ig_ref[...]                     # (1, Q)
    cum_c, ig_c = cum.T, ig.T                               # (Q, 1)
    tot = _last(cum)                                        # (1, 1)
    q, k = q_ref[...].astype(mxu), k_ref[...].astype(mxu)
    vi = v_ref[...].astype(f32) * ig_c
    vi_m = vi.astype(mxu)
    cmat, nv = c_scr[...], nv_scr[...]
    if save:
        cs_ref[...] = cmat.astype(cs_ref.dtype)
        ns_ref[...] = nv
    qe = q.astype(f32) * jnp.exp(cum_c)
    y = _dot(qe.astype(mxu), cmat.astype(mxu))              # (Q, hd)
    n = jnp.sum(qe * nv, axis=1, keepdims=True)             # (Q, 1)
    ys, ns = [], []
    for a in range(nq // t):
        ra = slice(a * t, (a + 1) * t)
        ya, na = y[ra], n[ra]
        for c in range(a + 1):
            rc = slice(c * t, (c + 1) * t)
            d = _decay(cum_c[ra], cum_ref[:, rc], a == c)
            s = _dot_nt(q[ra], k[rc]) * d
            ya = ya + _dot(s.astype(mxu), vi_m[rc])
            na = na + jnp.sum(s * ig_ref[:, rc], axis=1, keepdims=True)
        ys.append(ya)
        ns.append(na)
    y_ref[...] = jnp.concatenate(ys, axis=0).astype(y_ref.dtype)
    n_ref[...] = jnp.concatenate(ns, axis=0).T
    kw = k.astype(f32) * jnp.exp(tot - cum_c)
    g = jnp.exp(tot)
    c_scr[...] = g * cmat + _dot_tn(kw.astype(mxu), vi_m)
    nv_scr[...] = g * nv + jnp.sum(kw * ig_c, axis=0, keepdims=True)


def _bwd_kernel(q_ref, k_ref, v_ref, cum_ref, ig_ref, cs_ref, ns_ref,
                dy_ref, dn_ref, dq_ref, dk_ref, dv_ref, dig_ref, dcum_ref,
                dc_scr, dnv_scr, *, mxu):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dc_scr[...] = jnp.zeros_like(dc_scr)
        dnv_scr[...] = jnp.zeros_like(dnv_scr)

    nq = q_ref.shape[0]
    t = _tiles(nq)
    cum, ig, dn = cum_ref[...], ig_ref[...], dn_ref[...]    # (1, Q)
    cum_c, ig_c, dn_c = cum.T, ig.T, dn.T                   # (Q, 1)
    tot = _last(cum)
    e_c = jnp.exp(cum_c)
    w_c = jnp.exp(tot - cum_c)
    g = jnp.exp(tot)
    q, k = q_ref[...].astype(mxu), k_ref[...].astype(mxu)
    qf, kf = q.astype(f32), k.astype(f32)
    v = v_ref[...].astype(f32)
    vi_m = (v * ig_c).astype(mxu)
    dy = dy_ref[...].astype(mxu)
    cmat, nv = cs_ref[...].astype(mxu), ns_ref[...]
    dcm, dnv = dc_scr[...], dnv_scr[...]
    dcm_m = dcm.astype(mxu)
    # through the state: C and n into this chunk, dC and dn out of it
    dq = e_c * (_dot_nt(dy, cmat) + dn_c * nv)
    dk = w_c * (_dot_nt(vi_m, dcm_m) + ig_c * dnv)
    dvi = w_c * _dot(k, dcm_m)
    dig_c = w_c * jnp.sum(kf * dnv, axis=1, keepdims=True)
    dqs = [dq[a * t:(a + 1) * t] for a in range(nq // t)]
    dks = [dk[a * t:(a + 1) * t] for a in range(nq // t)]
    dvis = [dvi[a * t:(a + 1) * t] for a in range(nq // t)]
    digs = [jnp.zeros((1, t), f32) for _ in range(nq // t)]
    for a in range(nq // t):
        ra = slice(a * t, (a + 1) * t)
        for c in range(a + 1):
            rc = slice(c * t, (c + 1) * t)
            d = _decay(cum_c[ra], cum_ref[:, rc], a == c)
            s = _dot_nt(q[ra], k[rc]) * d
            dp = (_dot_nt(dy[ra], vi_m[rc]) + dn_c[ra] * ig_ref[:, rc]) * d
            dp_m = dp.astype(mxu)
            dqs[a] = dqs[a] + _dot(dp_m, k[rc])
            dks[c] = dks[c] + _dot_tn(dp_m, q[ra])
            dvis[c] = dvis[c] + _dot_tn(s.astype(mxu), dy[ra])
            digs[c] = digs[c] + jnp.sum(s * dn_c[ra], axis=0, keepdims=True)
    dq = jnp.concatenate(dqs, axis=0)
    dk = jnp.concatenate(dks, axis=0)
    dvi = jnp.concatenate(dvis, axis=0)
    dc_scr[...] = g * dcm + _dot_tn((qf * e_c).astype(mxu), dy)
    dnv_scr[...] = g * dnv + jnp.sum(qf * (e_c * dn_c), axis=0,
                                     keepdims=True)
    dq_ref[...] = dq.astype(dq_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = (dvi * ig_c).astype(dv_ref.dtype)
    dig_c = dig_c + jnp.sum(dvi * v, axis=1, keepdims=True)
    dig_ref[...] = jnp.concatenate(digs, axis=1) + dig_c.T
    dcum_ref[...] = jnp.sum(qf * dq - kf * dk, axis=1, keepdims=True).T


def _params(interpret):
    if interpret:
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)}


def _specs(h, hd, chunk, nc, reverse):
    """BlockSpecs of a (b, s, h·hd) sequence array, a (b, h, 1, s) row
    array, and the per-chunk (b, h, nc, ·, hd) state arrays; with
    ``reverse`` the chunks are walked last to first."""
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    seq = pl.BlockSpec((None, chunk, hd), lambda r, i, c: (r, at(c), i))
    row = pl.BlockSpec((None, None, 1, chunk),
                       lambda r, i, c: (r, i, 0, at(c)))
    state = lambda m: pl.BlockSpec((None, None, None, m, hd),
                                   lambda r, i, c: (r, i, at(c), 0, 0))
    return seq, row, state


def mlstm_gla_fwd_pallas(q, k, v, cum, ig, *, chunk, save, mxu,
                         interpret=False):
    """q, k, v (b, s, h·hd); cum, ig (b, h, 1, s) f32 → y (b, s, h·hd) in
    v's dtype and n̂ (b, h, 1, s) f32; with ``save`` also the state
    entering each chunk, C (b, h, nc, hd, hd) in ``mxu`` and n (b, h, nc,
    1, hd) f32."""
    b, s, w = q.shape
    h = cum.shape[1]
    hd, nc = w // h, s // chunk
    seq, row, state = _specs(h, hd, chunk, nc, reverse=False)
    out_shape = [jax.ShapeDtypeStruct((b, s, w), v.dtype),
                 jax.ShapeDtypeStruct((b, h, 1, s), f32)]
    out_specs = [seq, row]
    if save:
        out_shape += [jax.ShapeDtypeStruct((b, h, nc, hd, hd), mxu),
                      jax.ShapeDtypeStruct((b, h, nc, 1, hd), f32)]
        out_specs += [state(hd), state(1)]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, save=save, mxu=mxu),
        grid=(b, h, nc),
        in_specs=[seq, seq, seq, row, row],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hd, hd), f32), pltpu.VMEM((1, hd), f32)],
        name="mlstm_gla_fwd_res" if save else "mlstm_gla_fwd",
        **_params(interpret),
    )(q, k, v, cum, ig)


def mlstm_gla_bwd_pallas(q, k, v, cum, ig, cs, ns, dy, dn, *, chunk, mxu,
                         interpret=False):
    """The forward's inputs, its saved states, and the cotangents of y
    (b, s, h·hd) and n̂ (b, h, 1, s) → dq, dk, dv (b, s, h·hd) in their
    inputs' dtypes, and the input gate's cotangent and q·dq − k·dk, each
    (b, h, 1, s) f32."""
    b, s, w = q.shape
    h = cum.shape[1]
    hd, nc = w // h, s // chunk
    seq, row, state = _specs(h, hd, chunk, nc, reverse=True)
    vec = jax.ShapeDtypeStruct((b, h, 1, s), f32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, mxu=mxu),
        grid=(b, h, nc),
        in_specs=[seq, seq, seq, row, row, state(hd), state(1), seq, row],
        out_specs=[seq, seq, seq, row, row],
        out_shape=[jax.ShapeDtypeStruct((b, s, w), x.dtype)
                   for x in (q, k, v)] + [vec, vec],
        scratch_shapes=[pltpu.VMEM((hd, hd), f32), pltpu.VMEM((1, hd), f32)],
        name="mlstm_gla_bwd",
        **_params(interpret),
    )(q, k, v, cum, ig, cs, ns, dy, dn)
