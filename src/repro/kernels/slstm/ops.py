"""Public op for the sLSTM time recurrence, with its VJP.

On TPU this runs the Pallas kernels of ``slstm.py``; elsewhere the same
kernel bodies run in interpret mode. The recurrent products take their
operands in bfloat16 on the chip, as XLA's DEFAULT precision does for the
float32 einsum of ``ref.py`` there, and in float32 elsewhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.slstm.slstm import slstm_bwd_pallas, slstm_fwd_pallas


def _on_tpu():
    return jax.default_backend() == "tpu"


def _operand(r_gates, interpret):
    return r_gates if interpret else r_gates.astype(jnp.bfloat16)


def _forward(g_in, r_gates, state0, save):
    interpret = not _on_tpu()
    c0, n0, h0 = state0
    return slstm_fwd_pallas(g_in, _operand(r_gates, interpret), c0, n0, h0,
                            save=save, interpret=interpret)


@jax.custom_vjp
def slstm_scan(g_in, r_gates, state0):
    """g_in (b, s, 4d) f32 input-side gate pre-activations; r_gates
    (nh, hd, 4hd) f32; state0 (c, n, h) each (b, d) f32 → (hs (b, s, d)
    f32, (c, n, h) after the last step). Same math as ``slstm_scan_ref``."""
    hs, state, _ = _forward(g_in, r_gates, state0, save=False)
    return hs, state


def _slstm_scan_fwd(g_in, r_gates, state0):
    hs, state, res = _forward(g_in, r_gates, state0, save=True)
    return (hs, state), (hs, res, r_gates, state0[2])


def _slstm_scan_bwd(saved, cts):
    hs, (gs, cp, np_), r_gates, h0 = saved
    dhs, (dcT, dnT, dhT) = cts
    interpret = not _on_tpu()
    dg, dstate0 = slstm_bwd_pallas(
        dhs, gs, cp, np_, _operand(r_gates, interpret),
        dcT, dnT, dhT, interpret=interpret)
    b, s, _ = hs.shape
    nh, hd, f = r_gates.shape
    h_prev = jnp.concatenate([h0[:, None], hs[:, :-1]], axis=1)
    dr = jnp.einsum("bshd,bshf->hdf", h_prev.reshape(b, s, nh, hd),
                    dg.reshape(b, s, nh, f))
    return dg, dr, dstate0


# under remat, a forward whose residuals nobody reads runs the primal
# kernel, which writes none
slstm_scan.defvjp(_slstm_scan_fwd, _slstm_scan_bwd, optimize_remat=True)
