"""sLSTM block, plain float32 reference.

As the program defines it (``src/repro/models/xlstm.py``), pre-norm with
residual, x -> x + W_out(RMSNorm(h)), with the scalar-memory recurrence
run step by step over time (state c_0 = 0, n_0 = 1, h_0 = 0):

    g_t = x_t W_gates + reshape(h_{t-1} ⊗_heads R)      (4 gate slices)
    i = exp(min(ĩ, 10)),  f = sigmoid(f̃),  z = tanh(z̃),  o = sigmoid(õ)
    c_t = f c_{t-1} + i z,  n_t = f n_{t-1} + i,  h_t = o c_t / max(n_t, 1)

R is block-diagonal over heads, (nh, hd, 4·hd); its per-head output is
flattened head-major before the four gate slices are cut, exactly as the
program does. Departure from the xLSTM paper followed here: the clipped
exponential input gate and the max(n, 1) normaliser in place of the
paper's stabiliser state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.refs.common import F32, ONES, dense, rmsnorm

KIND = "slstm"


def spec(cfg):
    d, nh = cfg["d_model"], cfg["n_heads"]
    hd = d // nh
    return {
        "norm1": {"scale": ((d,), "model", ONES)},
        "inner": {
            "w_gates": ((d, 4 * d), "float32", dense(d)),
            "r_gates": ((nh, hd, 4 * hd), "float32", dense(nh, 0.1)),
            "w_out": ((d, d), "model", dense(d)),
            "out_norm": {"scale": ((d,), "model", ONES)},
        },
    }


def mix(p, x, cfg, num):
    d, nh = cfg["d_model"], cfg["n_heads"]
    hd = d // nh
    b = x.shape[0]
    g_in = num.mm(x, p["w_gates"])                          # (b, T, 4d)
    r = p["r_gates"].astype(F32)

    def step(state, g_t):
        c, n, h = state
        rec = num.ein("bhd,hdf->bhf", h.reshape(b, nh, hd), r)
        i_r, f_r, z_r, o_r = jnp.split(g_t + rec.reshape(b, 4 * d), 4,
                                       axis=-1)
        i = jnp.exp(jnp.minimum(i_r, 10.0))
        f = jax.nn.sigmoid(f_r)
        c = f * c + i * jnp.tanh(z_r)
        n = f * n + i
        h = jax.nn.sigmoid(o_r) * c / jnp.maximum(n, 1.0)
        return (c, n, h), h

    zeros = jnp.zeros((b, d), F32)
    _, hs = jax.lax.scan(step, (zeros, jnp.ones((b, d), F32), zeros),
                         g_in.transpose(1, 0, 2))
    y = rmsnorm(hs.transpose(1, 0, 2), p["out_norm"]["scale"],
                cfg["norm_eps"])
    return num.mm(y, p["w_out"])


def apply(p, x, cfg, num):
    return x + mix(p["inner"], rmsnorm(x, p["norm1"]["scale"],
                                       cfg["norm_eps"]), cfg, num)


def forward_flops(cfg, T):
    d, nh = cfg["d_model"], cfg["n_heads"]
    hd = d // nh
    return float(2 * T * (d * 4 * d + nh * hd * 4 * hd + d * d))
