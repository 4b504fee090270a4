"""Pure-jnp oracle for the sLSTM time recurrence.

The scalar-memory recurrence of ``repro.models.xlstm`` (state c, n, h,
each (b, d) f32), one step at a time over the sequence:

    g_t = g_in_t + reshape(h_{t-1} ⊗_heads R)       (b, 4d)
    i = exp(min(ĩ, 10)),  f = sigmoid(f̃),  z = tanh(z̃),  o = sigmoid(õ)
    c_t = f c_{t-1} + i z,  n_t = f n_{t-1} + i,  h_t = o c_t / max(n_t, 1)

R is block-diagonal over heads, (nh, hd, 4·hd); the per-head products
are flattened head-major, (b, nh·4hd), and added onto g_in's [i|f|z|o]
layout as they are.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

I_CLIP = 10.0                   # the exponential input gate's clip


def slstm_step_ref(state, g_t, r_gates):
    """One step: state (c, n, h) each (b, d) f32; g_t (b, 4d) the
    input-side gate pre-activations → (new state, h_t)."""
    nh, hd, _ = r_gates.shape
    c, n, h = state
    rec = jnp.einsum("bhd,hdf->bhf", h.reshape(-1, nh, hd), r_gates)
    g = g_t + rec.reshape(-1, 4 * nh * hd)
    i_r, f_r, z_r, o_r = jnp.split(g, 4, axis=-1)
    i_g = jnp.exp(jnp.minimum(i_r, I_CLIP))
    f_g = jax.nn.sigmoid(f_r)
    c = f_g * c + i_g * jnp.tanh(z_r)
    n = f_g * n + i_g
    h = jax.nn.sigmoid(o_r) * c / jnp.maximum(n, 1.0)
    return (c, n, h), h


def slstm_scan_ref(g_in, r_gates, state0):
    """g_in (b, s, 4d) f32; r_gates (nh, hd, 4hd) f32; state0 (c, n, h)
    → (hs (b, s, d) f32, final state)."""
    state, hs = jax.lax.scan(
        lambda st, g_t: slstm_step_ref(st, g_t, r_gates), state0,
        g_in.transpose(1, 0, 2))
    return hs.transpose(1, 0, 2), state
