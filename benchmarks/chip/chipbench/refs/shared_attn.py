"""Zamba2's shared transformer block, plain float32 reference.

One set of weights, reused at every position of the layer pattern that
names it (the decoder passes the segment's ``shared`` parameters). As the
program defines it (``src/repro/models/transformer.py``, ``attention.py``,
``mlp.py``), two pre-norm residual sublayers:

    x = x + W_o · softmax_causal(rope(h W_q) rope(h W_k)ᵀ / sqrt(hd)) h W_v
    x = x + W_down (SiLU(h W_gate) ⊙ h W_up)

with h the RMSNorm of the sublayer's input and grouped K/V heads. The
attention is computed over the whole sequence in blocks of query rows.
Departure from Zamba2 that the program makes and this reference follows:
the block sees only the residual stream, not its concatenation with the
input embeddings, and has no per-invocation LoRA adapters.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.refs.common import ONES, dense, query_blocks, rmsnorm, rope

KIND = "shared_attn"
SHARED = True          # one parameter set per segment, not one per repeat
QUERY_BLOCK = 256


def _dims(cfg):
    hd = cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]
    return cfg["n_heads"], cfg["n_kv_heads"], hd


def spec(cfg):
    d, f = cfg["d_model"], cfg["d_ff"]
    hq, hkv, hd = _dims(cfg)
    return {
        "norm1": {"scale": ((d,), "model", ONES)},
        "inner": {
            "wq": ((d, hq * hd), "model", dense(d)),
            "wk": ((d, hkv * hd), "model", dense(d)),
            "wv": ((d, hkv * hd), "model", dense(d)),
            "wo": ((hq * hd, d), "model", dense(hq * hd)),
        },
        "norm2": {"scale": ((d,), "model", ONES)},
        "ffn": {
            "w_gate": ((d, f), "model", dense(d)),
            "w_up": ((d, f), "model", dense(d)),
            "w_down": ((f, d), "model", dense(f)),
        },
    }


def attention(p, h, cfg, num):
    hq, hkv, hd = _dims(cfg)
    b, T, _ = h.shape
    g = hq // hkv
    theta = cfg["rope_theta"]
    q = rope(num.mm(h, p["wq"]).reshape(b, T, hq, hd), theta)
    k = rope(num.mm(h, p["wk"]).reshape(b, T, hkv, hd), theta)
    v = num.mm(h, p["wv"]).reshape(b, T, hkv, hd)
    q = q.reshape(b, T, hkv, g, hd) * hd ** -0.5
    nq, Q = query_blocks(T, QUERY_BLOCK)
    s_pos = jnp.arange(T)

    @jax.checkpoint
    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q, Q, axis=1)
        causal = (i * Q + jnp.arange(Q))[:, None] >= s_pos[None, :]
        s = num.ein("bqkgd,bskd->bkgqs", qb, k)
        s = jnp.where(causal, s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        return num.ein("bkgqs,bskd->bqkgd", a, v)

    o = jax.lax.map(block, jnp.arange(nq))                   # (nq, b, Q, ...)
    o = jnp.moveaxis(o, 0, 1).reshape(b, T, hq * hd)
    return num.mm(o, p["wo"])


def apply(p, x, cfg, num):
    eps = cfg["norm_eps"]
    x = x + attention(p["inner"], rmsnorm(x, p["norm1"]["scale"], eps),
                      cfg, num)
    h = rmsnorm(x, p["norm2"]["scale"], eps)
    f = p["ffn"]
    u = jax.nn.silu(num.mm(h, f["w_gate"])) * num.mm(h, f["w_up"])
    return x + num.mm(u, f["w_down"])


def forward_flops(cfg, T):
    d, f = cfg["d_model"], cfg["d_ff"]
    hq, hkv, hd = _dims(cfg)
    proj = 2 * T * (d * (hq + 2 * hkv) * hd + hq * hd * d + 3 * d * f)
    pairs = T * (T + 1) / 2
    return float(proj + hq * pairs * 2 * 2 * hd)
