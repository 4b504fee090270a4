"""Public op for the mLSTM's chunkwise gated linear attention, with its
VJP.

On TPU this runs the Pallas kernels of ``mlstm_gla.py``; elsewhere the
same kernel bodies run in interpret mode. The products take their
operands in bfloat16 on the chip, as XLA's DEFAULT precision does for the
float32 einsums of ``ref.py`` there, and in float32 elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.mlstm_gla.mlstm_gla import (mlstm_gla_bwd_pallas,
                                               mlstm_gla_fwd_pallas)


def _on_tpu():
    return jax.default_backend() == "tpu"


def _mxu(interpret):
    return jnp.float32 if interpret else jnp.bfloat16


def _rows(x):
    """(b, s, h) → (b, h, 1, s) f32: a lane-dense row per head."""
    return x.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, :]


def _unrows(x):
    return x[:, :, 0, :].transpose(0, 2, 1)


def _forward(q, k, v, i_gate, log_f, chunk, save):
    b, s, h, hd = q.shape
    interpret = not _on_tpu()
    flat = lambda x: x.reshape(b, s, h * hd)
    cum = jnp.cumsum(log_f.reshape(b, s // chunk, chunk, h),
                     axis=2).reshape(b, s, h)
    ins = (flat(q), flat(k), flat(v), _rows(cum), _rows(i_gate))
    y, n, *states = mlstm_gla_fwd_pallas(
        *ins, chunk=chunk, save=save, mxu=_mxu(interpret),
        interpret=interpret)
    return (y.reshape(b, s, h, hd), _unrows(n)), (ins, states)


def mlstm_gla(q, k, v, i_gate, log_f, chunk):
    """q, k, v (b, s, h, hd); i_gate, log_f (b, s, h) f32; s a multiple of
    ``chunk`` → y (b, s, h, hd) in v's dtype and the normaliser n̂ (b, s,
    h) f32. Same math as ``mlstm_gla_ref``."""
    return _op(chunk)(q, k, v, i_gate, log_f)


@functools.cache
def _op(chunk):
    """The custom VJP for one chunk length (closed over, not a
    ``nondiff_argnums`` argument, which the remat optimisation does not
    take)."""
    @jax.custom_vjp
    def op(q, k, v, i_gate, log_f):
        out, _ = _forward(q, k, v, i_gate, log_f, chunk, save=False)
        return out

    def fwd(q, k, v, i_gate, log_f):
        return _forward(q, k, v, i_gate, log_f, chunk, save=True)

    def bwd(saved, cts):
        return _backward(chunk, saved, cts)

    # under remat, a forward whose residuals nobody reads runs the primal
    # kernel, which writes none
    op.defvjp(fwd, bwd, optimize_remat=True)
    return op


def _backward(chunk, saved, cts):
    (q, k, v, cum, ig), (cs, ns) = saved
    dy, dn = cts
    b, s, w = q.shape
    interpret = not _on_tpu()
    dq, dk, dv, dig, dcum = mlstm_gla_bwd_pallas(
        q, k, v, cum, ig, cs, ns, dy.reshape(b, s, w).astype(v.dtype),
        _rows(dn), chunk=chunk, mxu=_mxu(interpret), interpret=interpret)
    # y depends on log f through the global cumulative sum c, and
    # ∂/∂c_t = q_t·dq_t − k_t·dk_t; log f_t feeds every c_{t' ≥ t}
    dlog_f = jax.lax.cumsum(_unrows(dcum), axis=1, reverse=True)
    shape = dy.shape
    return (dq.reshape(shape), dk.reshape(shape), dv.reshape(shape),
            _unrows(dig), dlog_f)
