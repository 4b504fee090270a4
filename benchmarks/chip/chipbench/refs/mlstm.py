"""mLSTM block, plain float32 reference.

As the program defines it (``src/repro/models/xlstm.py``), pre-norm with
residual, x -> x + W_down(RMSNorm(h) * SiLU(z)):

    [u, z] = x W_up;  c = SiLU(causal_conv4(u))
    q = c W_q / sqrt(hd),  k = c W_k / sqrt(hd),  v = u W_v   (nh heads)
    [ĩ, f̃] = u W_gates;  i = sigmoid(ĩ),  log f = log_sigmoid(f̃)
    C_t = f_t C_{t-1} + i_t k_t v_tᵀ,   n_t = f_t n_{t-1} + i_t k_t
    h_t = (q_tᵀ C_t) / max(|q_tᵀ n_t|, 1)

computed here in its parallel (quadratic) form over the whole sequence,
D_ts = exp(Σ_{s<r≤t} log f_r) for s ≤ t, not in the program's chunked
scan. Departures from the xLSTM paper that the program makes and this
reference follows: sigmoid input gate instead of the stabilised
exponential one, and the normaliser bound max(|n|, 1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.refs.common import F32, ONES, causal_conv, dense, rmsnorm

KIND = "mlstm"


def _dims(cfg):
    d_in = 2 * cfg["d_model"]
    nh = cfg["n_heads"]
    return d_in, nh, d_in // nh


def spec(cfg):
    d = cfg["d_model"]
    d_in, nh, _ = _dims(cfg)
    return {
        "norm1": {"scale": ((d,), "model", ONES)},
        "inner": {
            "w_up": ((d, 2 * d_in), "model", dense(d)),
            "conv_w": ((4, d_in), "model", dense(4)),
            "wq": ((d_in, d_in), "model", dense(d_in)),
            "wk": ((d_in, d_in), "model", dense(d_in)),
            "wv": ((d_in, d_in), "model", dense(d_in)),
            "w_gates": ((d_in, 2 * nh), "float32", dense(d_in)),
            "out_norm": {"scale": ((d_in,), "model", ONES)},
            "w_down": ((d_in, d), "model", dense(d_in)),
        },
    }


def mix(p, x, cfg, num):
    d_in, nh, hd = _dims(cfg)
    b, T, _ = x.shape
    u, z = jnp.split(num.mm(x, p["w_up"]), 2, axis=-1)
    c = causal_conv(u, p["conv_w"].astype(F32))
    q = num.mm(c, p["wq"]).reshape(b, T, nh, hd) * hd ** -0.5
    k = num.mm(c, p["wk"]).reshape(b, T, nh, hd) * hd ** -0.5
    v = num.mm(u, p["wv"]).reshape(b, T, nh, hd)
    gates = num.mm(u, p["w_gates"])
    i_g = jax.nn.sigmoid(gates[..., :nh])                    # (b, T, nh)
    cum = jnp.cumsum(jax.nn.log_sigmoid(gates[..., nh:]), axis=1)
    causal = jnp.tril(jnp.ones((T, T), bool))
    diff = cum.transpose(0, 2, 1)[:, :, :, None] \
        - cum.transpose(0, 2, 1)[:, :, None, :]              # (b, nh, t, s)
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    w = num.ein("bthk,bshk->bhts", q, k) * decay \
        * i_g.transpose(0, 2, 1)[:, :, None, :]
    y = num.ein("bhts,bshv->bthv", w, v)
    n = jnp.sum(w, axis=-1).transpose(0, 2, 1)[..., None]    # (b, T, nh, 1)
    h = (y / jnp.maximum(jnp.abs(n), 1.0)).reshape(b, T, d_in)
    h = rmsnorm(h, p["out_norm"]["scale"], cfg["norm_eps"]) * jax.nn.silu(z)
    return num.mm(h, p["w_down"])


def apply(p, x, cfg, num):
    return x + mix(p["inner"], rmsnorm(x, p["norm1"]["scale"],
                                       cfg["norm_eps"]), cfg, num)


def forward_flops(cfg, T):
    """Model FLOPs of one row of T tokens through one layer: the
    projections, and the recurrence in the chunked form with the
    configuration's chunk (causal pairs within a chunk, one state read
    and one state update per token across chunks)."""
    d = cfg["d_model"]
    d_in, nh, hd = _dims(cfg)
    Q = min(cfg["ssm"]["chunk"], T)
    proj = 2 * T * (d * 2 * d_in + 3 * d_in * d_in + d_in * 2 * nh
                    + d_in * d)
    pairs = T * (Q + 1) / 2
    mixing = nh * (pairs * 2 * (hd + hd + 1) + T * 2 * 2 * hd * (hd + 1))
    return float(proj + mixing)
