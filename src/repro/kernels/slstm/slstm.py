"""The sLSTM time recurrence as Pallas TPU kernels.

Forward: the grid walks time blocks in order ("arbitrary" semantics).
The state (c, n, h) is carried between blocks in VMEM scratch, the
recurrent weight R stays in VMEM for the whole call, and an in-kernel
loop runs the block's steps. g_in is streamed in (b, t_blk, 4d) blocks
of its (b, s, 4d) layout and h goes back the same way; each block is
turned step-major in VMEM first (sublane-strided stores into a scratch
whose rows are padded to the sublane count), so that a step's rows load
as whole vector registers. Read straight from a (b, t_blk, ·) block they
would sit one row to a register.

Backward: the reverse recurrence for (dh, dc, dn) only. It walks the time
blocks last to first and emits every step's gate cotangent dg_t (b, 4d);
the weight gradient dR = Σ_t h_{t−1}ᵀ·dg_t is one contraction after the
loop (``ops.py``), never a loop carry. The forward's residuals for it
(each step's gate pre-activations and pre-step c, n) are kept step-major
and padded, as the loop reads them.

State and gate arithmetic are float32. The recurrent products take their
operands in R's dtype and accumulate in float32: ``ops.py`` passes R as
bfloat16 on the chip, as XLA's DEFAULT precision rounds float32 operands
there, and float32 elsewhere, where the reference's products are exact.
Both operand paths run in interpret mode, so the packed bfloat16 one is
tested on the CPU too. A ragged last time block runs only its valid
steps.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.slstm.ref import I_CLIP

BLOCK_ROWS = 128    # t_blk · b: a (b, t_blk, 4d) f32 block, 2 MiB at d = 1024
SUBLANES = 8
VMEM_LIMIT = 64 << 20


def time_block(b, s):
    """Steps per grid block: the largest power of two with at most
    BLOCK_ROWS rows, and at least 8 (a block's second-minor dim), or the
    whole sequence when shorter."""
    return min(s, max(8, 1 << max(0, (BLOCK_ROWS // b).bit_length() - 1)))


def padded_rows(b):
    return -(-b // SUBLANES) * SUBLANES


def _weights_scratch(r):
    """bfloat16 R is kept in VMEM as uint32, two rows to a word: the
    packing of a bfloat16 vector register, so that each step loads it in
    the MXU's operand layout as it is (a bfloat16 VMEM array is tiled
    (8, 128) and would be repacked at every step). Float32 R (interpret
    mode) is read as it is."""
    if r.dtype != jnp.bfloat16:
        return []
    nh, hd, f = r.shape
    return [pltpu.VMEM((nh, hd // 2, f), jnp.uint32)]


def _pack(r_ref, w_scr):
    for k in range(r_ref.shape[0]):
        w_scr[k] = pltpu.bitcast(r_ref[k], jnp.uint32)


def _weights(w_ref, k):
    w = w_ref[k]
    return pltpu.bitcast(w, jnp.bfloat16) if w.dtype == jnp.uint32 else w


def _rec(h, w_ref):
    """(b, d) · blockdiag(R) → (b, nh·4hd), head-major."""
    ws = [_weights(w_ref, k) for k in range(w_ref.shape[0])]
    hd = ws[0].shape[0]
    hq = h.astype(ws[0].dtype)
    return jnp.concatenate(
        [jnp.dot(hq[:, k * hd:(k + 1) * hd], w,
                 preferred_element_type=jnp.float32)
         for k, w in enumerate(ws)], axis=-1)


def _rec_t(dg, w_ref):
    """(b, nh·4hd) · blockdiag(R)ᵀ → (b, d)."""
    ws = [_weights(w_ref, k) for k in range(w_ref.shape[0])]
    f = ws[0].shape[1]
    dq = dg.astype(ws[0].dtype)
    return jnp.concatenate(
        [jax.lax.dot_general(dq[:, k * f:(k + 1) * f], w,
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
         for k, w in enumerate(ws)], axis=-1)


def _gates(g, d):
    i_r = g[:, :d]
    i = jnp.exp(jnp.minimum(i_r, I_CLIP))
    f = jax.nn.sigmoid(g[:, d:2 * d])
    z = jnp.tanh(g[:, 2 * d:3 * d])
    o = jax.nn.sigmoid(g[:, 3 * d:])
    return i_r, i, f, z, o


def _steps(s, tb, blk):
    """Valid steps of time block ``blk``: static unless the last block is
    ragged."""
    return tb if s % tb == 0 else jnp.minimum(tb, s - blk * tb)


# A step-major scratch holds a block as (lane tiles, t_blk · bp, ln): step
# t's rows are [t·bp, (t+1)·bp), the lanes cut into tiles of ln, so that a
# batch row's block goes in with one sublane-strided store per tile.
def _step_major_scratch(tb, bp, w):
    ln = math.gcd(w, 128)
    return pltpu.VMEM((w // ln, tb * bp, ln), jnp.float32)


def _step_major(scr, ref, bp):
    """Batch-major block ref (b, t_blk, w) → step-major scratch."""
    b, tb, _ = ref.shape
    ln = scr.shape[-1]

    def row(bi, _):
        for k in range(scr.shape[0]):
            scr[k, pl.ds(bi, tb, stride=bp), :] = \
                ref[bi, :, k * ln:(k + 1) * ln]
    jax.lax.fori_loop(0, b, row, None)


def _batch_major(ref, scr, bp):
    """Step-major scratch → batch-major block ref (b, t_blk, w)."""
    b, tb, _ = ref.shape
    ln = scr.shape[-1]

    def row(bi, _):
        for k in range(scr.shape[0]):
            ref[bi, :, k * ln:(k + 1) * ln] = \
                scr[k, pl.ds(bi, tb, stride=bp), :]
    jax.lax.fori_loop(0, b, row, None)


def _rows(t, bp):
    return pl.ds(pl.multiple_of(t * bp, bp), bp)


def _get(scr, t, bp):
    return jnp.concatenate([scr[k, _rows(t, bp), :]
                            for k in range(scr.shape[0])], axis=-1)


def _set(scr, t, bp, v):
    ln = scr.shape[-1]
    for k in range(scr.shape[0]):
        scr[k, _rows(t, bp), :] = v[:, k * ln:(k + 1) * ln]


def _init_state(scrs, refs, b):
    """State scratch (bp, d) ← the (b, d) state; the pad rows 0."""
    for scr, ref in zip(scrs, refs):
        scr[...] = jnp.zeros_like(scr)
        scr[:b] = ref[...]


def _fwd_kernel(g_ref, r_ref, c0_ref, n0_ref, h0_ref, *refs, s, save):
    if save:
        (hs_ref, gs_ref, cp_ref, np_ref, cT_ref, nT_ref, hT_ref,
         g_scr, h_scr, c_scr, n_scr, hc_scr, *w_scr) = refs
    else:
        (hs_ref, cT_ref, nT_ref, hT_ref,
         g_scr, h_scr, c_scr, n_scr, hc_scr, *w_scr) = refs
    w_ref = w_scr[0] if w_scr else r_ref
    j = pl.program_id(0)
    b, tb, _ = g_ref.shape
    bp, d = c_scr.shape

    @pl.when(j == 0)
    def _():
        if w_scr:
            _pack(r_ref, w_ref)
        g_scr[...] = jnp.zeros_like(g_scr)      # the pad rows stay 0
        _init_state((c_scr, n_scr, hc_scr), (c0_ref, n0_ref, h0_ref), b)

    _step_major(g_scr, g_ref, bp)

    def step(t, carry):
        c, n, h = carry
        g = _get(g_scr, t, bp) + _rec(h, w_ref)
        if save:
            gs_ref[t] = g
            cp_ref[t] = c
            np_ref[t] = n
        _, i, f, z, o = _gates(g, d)
        c = f * c + i * z
        n = f * n + i
        h = o * c / jnp.maximum(n, 1.0)
        _set(h_scr, t, bp, h)
        return c, n, h

    c, n, h = jax.lax.fori_loop(0, _steps(s, tb, j), step,
                                (c_scr[...], n_scr[...], hc_scr[...]))
    c_scr[...] = c
    n_scr[...] = n
    hc_scr[...] = h
    _batch_major(hs_ref, h_scr, bp)

    @pl.when(j == pl.num_programs(0) - 1)
    def _():
        cT_ref[...] = c[:b]
        nT_ref[...] = n[:b]
        hT_ref[...] = h[:b]


def _tie(x, edge, below):
    """d min/max(x, edge)/dx as JAX differentiates it: 1 on the side that
    passes x, 0 on the other, 0.5 at a tie."""
    return jnp.where(x == edge, 0.5, jnp.where(below, 1.0, 0.0))


def _bwd_kernel(dhs_ref, gs_ref, cp_ref, np_ref, r_ref, dcT_ref, dnT_ref,
                dhT_ref, dg_ref, dc0_ref, dn0_ref, dh0_ref,
                dhs_scr, dg_scr, dc_scr, dn_scr, dh_scr, *w_scr, s):
    w_ref = w_scr[0] if w_scr else r_ref
    j = pl.program_id(0)
    nblk = pl.num_programs(0)
    b, tb, _ = dhs_ref.shape
    bp, d = dc_scr.shape

    @pl.when(j == 0)
    def _():
        if w_scr:
            _pack(r_ref, w_ref)
        dhs_scr[...] = jnp.zeros_like(dhs_scr)  # the pad rows stay 0
        _init_state((dc_scr, dn_scr, dh_scr), (dcT_ref, dnT_ref, dhT_ref), b)

    _step_major(dhs_scr, dhs_ref, bp)
    steps = _steps(s, tb, nblk - 1 - j)

    def step(k, carry):
        dc, dn, dh = carry
        t = steps - 1 - k
        g = gs_ref[t]
        c_prev = cp_ref[t]
        n_prev = np_ref[t]
        i_r, i, f, z, o = _gates(g, d)
        c = f * c_prev + i * z
        n = f * n_prev + i
        m = jnp.maximum(n, 1.0)
        dh = dh + _get(dhs_scr, t, bp)
        # h = (o · c) / m
        dq = dh / m
        do = dq * c
        dc = dc + dq * o
        dm = -dh * (o * c) / (m * m)
        dn = dn + dm * _tie(n, 1.0, n > 1.0)
        di = dc * z + dn
        df = dc * c_prev + dn * n_prev
        dz = dc * i
        dg = jnp.concatenate([
            di * i * _tie(i_r, I_CLIP, i_r < I_CLIP),
            df * (f * (1.0 - f)),
            dz * (1.0 - z * z),
            do * (o * (1.0 - o))], axis=-1)
        _set(dg_scr, t, bp, dg)
        return dc * f, dn * f, _rec_t(dg, w_ref)

    dc, dn, dh = jax.lax.fori_loop(0, steps, step,
                                   (dc_scr[...], dn_scr[...], dh_scr[...]))
    dc_scr[...] = dc
    dn_scr[...] = dn
    dh_scr[...] = dh
    _batch_major(dg_ref, dg_scr, bp)

    @pl.when(j == nblk - 1)
    def _():
        dc0_ref[...] = dc[:b]
        dn0_ref[...] = dn[:b]
        dh0_ref[...] = dh[:b]


def _params(interpret):
    if interpret:
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT)}


def slstm_fwd_pallas(g_in, r_gates, c0, n0, h0, *, save, interpret=False):
    """g_in (b, s, 4d) f32; r_gates (nh, hd, 4hd) f32 or bfloat16; c0, n0,
    h0 (b, d) f32 → hs (b, s, d), (c, n, h) at s, and with ``save`` the
    backward's residuals, step-major with the rows padded to bp: g
    (s, bp, 4d) and the pre-step c, n (s, bp, d)."""
    b, s, f4 = g_in.shape
    d = f4 // 4
    tb = time_block(b, s)
    bp = padded_rows(b)
    seq = lambda w: pl.BlockSpec((b, tb, w), lambda j: (0, j, 0))
    res = lambda w: pl.BlockSpec((tb, bp, w), lambda j: (j, 0, 0))
    whole = pl.BlockSpec((b, d), lambda j: (0, 0))
    out_shape = [jax.ShapeDtypeStruct((b, s, d), jnp.float32)]
    out_specs = [seq(d)]
    if save:
        out_shape += [jax.ShapeDtypeStruct((s, bp, w), jnp.float32)
                      for w in (f4, d, d)]
        out_specs += [res(f4), res(d), res(d)]
    state = jax.ShapeDtypeStruct((b, d), jnp.float32)
    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, s=s, save=save),
        grid=(pl.cdiv(s, tb),),
        in_specs=[seq(f4), pl.BlockSpec(memory_space=pltpu.VMEM),
                  whole, whole, whole],
        out_specs=out_specs + [whole] * 3,
        out_shape=out_shape + [state] * 3,
        scratch_shapes=[_step_major_scratch(tb, bp, f4),
                        _step_major_scratch(tb, bp, d)]
        + [pltpu.VMEM((bp, d), jnp.float32)] * 3 + _weights_scratch(r_gates),
        name="slstm_fwd",
        **_params(interpret),
    )(g_in, r_gates, c0, n0, h0)
    return outs[0], tuple(outs[-3:]), tuple(outs[1:-3])


def slstm_bwd_pallas(dhs, gs, cp, np_, r_gates, dcT, dnT, dhT, *,
                     interpret=False):
    """Reverse recurrence: the cotangent of hs (b, s, d), the forward's
    residuals, R, and the final state's cotangents → dg (b, s, 4d) (the
    cotangent of g_in) and the initial state's (dc0, dn0, dh0)."""
    b, s, d = dhs.shape
    f4 = 4 * d
    tb = time_block(b, s)
    bp = padded_rows(b)
    nblk = pl.cdiv(s, tb)
    seq = lambda w: pl.BlockSpec((b, tb, w), lambda j: (0, nblk - 1 - j, 0))
    res = lambda w: pl.BlockSpec((tb, bp, w), lambda j: (nblk - 1 - j, 0, 0))
    whole = pl.BlockSpec((b, d), lambda j: (0, 0))
    state = jax.ShapeDtypeStruct((b, d), jnp.float32)
    dg, dc0, dn0, dh0 = pl.pallas_call(
        functools.partial(_bwd_kernel, s=s),
        grid=(nblk,),
        in_specs=[seq(d), res(f4), res(d), res(d),
                  pl.BlockSpec(memory_space=pltpu.VMEM),
                  whole, whole, whole],
        out_specs=[seq(f4), whole, whole, whole],
        out_shape=[jax.ShapeDtypeStruct((b, s, f4), jnp.float32),
                   state, state, state],
        scratch_shapes=[_step_major_scratch(tb, bp, d),
                        _step_major_scratch(tb, bp, f4)]
        + [pltpu.VMEM((bp, d), jnp.float32)] * 3 + _weights_scratch(r_gates),
        name="slstm_bwd",
        **_params(interpret),
    )(dhs, gs, cp, np_, r_gates, dcT, dnT, dhT)
    return dg, (dc0, dn0, dh0)
