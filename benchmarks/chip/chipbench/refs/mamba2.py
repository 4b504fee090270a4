"""Mamba2 (SSD) block, plain float32 reference.

As the program defines it (``src/repro/models/ssm.py``), pre-norm with
residual, x -> x + W_out(RMSNorm(y) * SiLU(z)), one B/C group shared by
all heads:

    [z, x̃] = x W_zx;  [B̃C̃, dt̃] = x W_bcdt
    x̃ = SiLU(conv4(x̃));  [B, C] = SiLU(conv4(B̃C̃))
    Δ = softplus(dt̃ + dt_bias),  a = -exp(a_log)          (per head)
    S_t = exp(a Δ_t) S_{t-1} + B_t (Δ_t x̃_t)ᵀ,   y_t = C_tᵀ S_t + D x̃_t

computed here in its quadratic form, y_t = Σ_{s≤t} exp(L_t − L_s)
(C_t·B_s) Δ_s x̃_s with L the running sum of a Δ, in blocks of query
positions so that the (heads, t, s) decay fits; the program runs a
chunked scan instead.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.refs.common import (F32, ONES, ZEROS, causal_conv, dense,
                                   query_blocks, rmsnorm)

KIND = "mamba2"
QUERY_BLOCK = 128


def _dims(cfg):
    s = cfg["ssm"]
    d_in = s["expand"] * cfg["d_model"]
    return d_in, d_in // s["head_dim"], s["head_dim"], s["d_state"]


def spec(cfg):
    d = cfg["d_model"]
    d_in, nh, _, ds = _dims(cfg)
    kc = cfg["ssm"]["d_conv"]
    return {
        "norm1": {"scale": ((d,), "model", ONES)},
        "inner": {
            "w_zx": ((d, 2 * d_in), "model", dense(d)),
            "w_bcdt": ((d, 2 * ds + nh), "model", dense(d)),
            "conv_x": ((kc, d_in), "model", dense(kc)),
            "conv_bc": ((kc, 2 * ds), "model", dense(kc)),
            "a_log": ((nh,), "float32", ("log_linspace", 1.0, 16.0)),
            "dt_bias": ((nh,), "float32", ZEROS),
            "d_skip": ((nh,), "float32", ONES),
            "out_norm": {"scale": ((d_in,), "model", ONES)},
            "w_out": ((d_in, d), "model", dense(d_in)),
        },
    }


def mix(p, x, cfg, num):
    d_in, nh, hd, ds = _dims(cfg)
    b, T, _ = x.shape
    z, xs = jnp.split(num.mm(x, p["w_zx"]), 2, axis=-1)
    bcdt = num.mm(x, p["w_bcdt"])
    xs = causal_conv(xs, p["conv_x"].astype(F32))
    bc = causal_conv(bcdt[..., :2 * ds], p["conv_bc"].astype(F32))
    Bm, Cm = bc[..., :ds], bc[..., ds:]
    dt = jax.nn.softplus(bcdt[..., 2 * ds:] + p["dt_bias"])  # (b, T, nh)
    L = jnp.cumsum(-jnp.exp(p["a_log"]) * dt, axis=1)        # (b, T, nh)
    xh = xs.reshape(b, T, nh, hd)
    v = xh * dt[..., None]
    nq, Q = query_blocks(T, QUERY_BLOCK)
    s_pos = jnp.arange(T)

    @jax.checkpoint
    def block(i):
        t0 = i * Q
        Cq = jax.lax.dynamic_slice_in_dim(Cm, t0, Q, axis=1)
        Lq = jax.lax.dynamic_slice_in_dim(L, t0, Q, axis=1)
        causal = (t0 + jnp.arange(Q))[:, None] >= s_pos[None, :]  # (Q, T)
        cb = num.ein("bqn,bsn->bqs", Cq, Bm)
        diff = Lq[:, :, None, :] - L[:, None, :, :]          # (b, Q, T, nh)
        decay = jnp.exp(jnp.where(causal[None, :, :, None], diff, -jnp.inf))
        return num.ein("bqsh,bshp->bqhp", cb[..., None] * decay, v)

    y = jax.lax.map(block, jnp.arange(nq))                   # (nq, b, Q, nh, hd)
    y = jnp.moveaxis(y, 0, 1).reshape(b, T, nh, hd)
    y = y + xh * p["d_skip"][:, None]
    y = rmsnorm(y.reshape(b, T, d_in), p["out_norm"]["scale"],
                cfg["norm_eps"]) * jax.nn.silu(z)
    return num.mm(y, p["w_out"])


def apply(p, x, cfg, num):
    return x + mix(p["inner"], rmsnorm(x, p["norm1"]["scale"],
                                       cfg["norm_eps"]), cfg, num)


def forward_flops(cfg, T):
    """Projections, and the SSD recurrence in the chunked form with the
    configuration's chunk: per causal pair within a chunk one C·B product
    (shared by the heads) and one weighted value per head; per token and
    head one state read and one state update."""
    d = cfg["d_model"]
    d_in, nh, hd, ds = _dims(cfg)
    Q = min(cfg["ssm"]["chunk"], T)
    proj = 2 * T * (d * 2 * d_in + d * (2 * ds + nh) + d_in * d)
    pairs = T * (Q + 1) / 2
    mixing = pairs * 2 * ds + nh * (pairs * 2 * hd + T * 2 * 2 * ds * hd)
    return float(proj + mixing)
