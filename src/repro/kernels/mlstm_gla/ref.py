"""Pure-jnp oracle for the mLSTM's gated linear attention.

The matrix memory of ``repro.models.xlstm``'s mLSTM, per head (C (hd, hd)
and the normaliser state n (hd), both starting at 0):

    C_t = f_t C_{t-1} + i_t k_t v_tᵀ,   n_t = f_t n_{t-1} + i_t k_t
    y_t = q_tᵀ C_t,                     n̂_t = q_tᵀ n_t

with f_t = exp(log_f_t). It is the shared ``chunked_gla`` scan with the
value augmented by the input gate as one more channel, [v·i | i], so that
the normaliser rides along as the last value column.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.models.ssm import chunked_gla


def augment_value(v, i_gate):
    """[v·i | i]: v (b, s, h, hd), i_gate (b, s, h) → (b, s, h, hd + 1)
    in v's dtype."""
    i = i_gate[..., None].astype(v.dtype)
    return jnp.concatenate([v * i, i], -1)


def mlstm_gla_ref(q, k, v, i_gate, log_f, chunk):
    """q, k, v (b, s, h, hd); i_gate, log_f (b, s, h) f32 → y (b, s, h,
    hd) and n̂ (b, s, h), both in v's dtype."""
    y_aug, _ = chunked_gla(q, k, augment_value(v, i_gate), log_f,
                           chunk=chunk)
    return y_aug[..., :-1], y_aug[..., -1]
