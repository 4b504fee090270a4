"""Pieces shared by the block references: parameter specs, norms, the
causal depthwise convolution, rotary embeddings.

A parameter spec is ``(shape, dtype, init)``: ``dtype`` is ``"model"``
(the configuration's ``dtype``) or ``"float32"``; ``init`` is
``("normal", std)`` (truncated at two standard deviations), ``("ones",)``,
``("zeros",)`` or ``("log_linspace", lo, hi)``. The scales are those the
program's own initialiser uses, so a run starts where a user's would.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def dense(fan_in, gain=1.0):
    return ("normal", gain / float(fan_in) ** 0.5)


ONES = ("ones",)
ZEROS = ("zeros",)


def rmsnorm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def causal_conv(u, w):
    """out_t = sum_i u_{t-K+1+i} w_i (zero left padding), then SiLU.
    u: (b, T, c); w: (K, c)."""
    K, T = w.shape[0], u.shape[1]
    up = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(up[:, i:i + T] * w[i] for i in range(K))
    return jax.nn.silu(out)


def rope(x, theta):
    """Rotary embedding on (b, T, heads, hd), halves rotated as pairs
    (x_i, x_{i+hd/2}) at frequency theta^(-i/(hd/2)), positions 0..T-1."""
    T, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def query_blocks(T, q):
    """Static split of T query positions into blocks of ``q`` (the last
    may be shorter only if q does not divide T; callers pass divisors)."""
    q = min(q, T)
    assert T % q == 0, (T, q)
    return T // q, q
