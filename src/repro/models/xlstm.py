"""xLSTM blocks: mLSTM (matrix memory, chunkwise-parallel) and sLSTM
(scalar memory, sequential scan).

The mLSTM recurrence  C_t = f_t C_{t-1} + i_t v_t k_t^T,  n_t = f_t n_{t-1}
+ i_t k_t  is expressed through the shared ``chunked_gla`` machinery by
augmenting the value vector with a constant-one column so the normaliser n
rides along as the last value channel; on TPU, without a cache, it runs as
a Pallas kernel pair instead (``repro.kernels.mlstm_gla``). Gates use
sigmoid activations (a stabilised simplification of the paper's
exponential gating — see DESIGN.md §8).

sLSTM keeps per-head scalar state with block-diagonal recurrent weights and
is inherently sequential: a Pallas kernel over time on TPU
(``repro.kernels.slstm``), ``lax.scan`` elsewhere.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.mlstm_gla.ref import augment_value, mlstm_gla_ref
from repro.kernels.slstm.ref import slstm_scan_ref
from repro.models.common import dense_init, dtype_of, rmsnorm, split_key
from repro.models.ssm import _mamba_conv, chunked_gla, gla_step


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------
def _mlstm_dims(cfg):
    d_in = 2 * cfg.d_model
    nh = cfg.n_heads
    hd = d_in // nh
    return d_in, nh, hd


def init_mlstm(key, cfg):
    d = cfg.d_model
    d_in, nh, hd = _mlstm_dims(cfg)
    dt = dtype_of(cfg)
    k1, k2, k3, k4, k5, k6, k7 = split_key(key, 7)
    return {
        "w_up": dense_init(k1, (d, 2 * d_in), dt),          # [u, z]
        "conv_w": dense_init(k2, (4, d_in), dt),
        "wq": dense_init(k3, (d_in, d_in), dt),
        "wk": dense_init(k4, (d_in, d_in), dt),
        "wv": dense_init(k5, (d_in, d_in), dt),
        "w_gates": dense_init(k6, (d_in, 2 * nh), jnp.float32),  # i, f per head
        "out_norm": {"scale": jnp.ones((d_in,), dt)},
        "w_down": dense_init(k7, (d_in, d), dt),
    }


def mlstm_cache_init(cfg, batch, dtype):
    d_in, nh, hd = _mlstm_dims(cfg)
    return {
        "conv": jnp.zeros((batch, 3, d_in), dtype),
        "H": jnp.zeros((batch, nh, hd, hd + 1), jnp.float32),  # [C | n]
    }


def _mlstm_qkvga(params, x, cfg):
    d_in, nh, hd = _mlstm_dims(cfg)
    b, s, _ = x.shape
    uz = x @ params["w_up"]
    u, z = jnp.split(uz, 2, axis=-1)
    return u, z


def apply_mlstm(params, x, *, cfg, cache=None):
    d_in, nh, hd = _mlstm_dims(cfg)
    b, s, _ = x.shape
    u, z = _mlstm_qkvga(params, x, cfg)
    cu, new_conv = _mamba_conv(u, params["conv_w"],
                               None if cache is None else cache["conv"])
    q = (cu @ params["wq"]).reshape(b, s, nh, hd) * hd ** -0.5
    k = (cu @ params["wk"]).reshape(b, s, nh, hd) * hd ** -0.5
    v = (u @ params["wv"]).reshape(b, s, nh, hd)
    gates = u.astype(jnp.float32) @ params["w_gates"]
    i_g = jax.nn.sigmoid(gates[..., :nh])                    # (b,s,nh)
    log_f = jax.nn.log_sigmoid(gates[..., nh:])
    chunk = min(cfg.ssm.chunk, s)

    new_H = None
    if cache is None and _use_gla_kernel(s, chunk, hd):
        from repro.kernels.mlstm_gla.ops import mlstm_gla
        y, n = _per_shard(functools.partial(mlstm_gla, chunk=chunk), b,
                          (_ROWS,) * 5, (_ROWS,) * 2)(q, k, v, i_g, log_f)
    elif cache is None:
        y, n = mlstm_gla_ref(q, k, v, i_g, log_f, chunk)
    else:
        v_aug = augment_value(v, i_g)
        if s == 1:
            y_aug, new_H = gla_step(q, k, v_aug, log_f, cache["H"])
        else:  # prefill into an existing state
            y_aug, new_H = chunked_gla(q, k, v_aug, log_f, h0=cache["H"],
                                       chunk=chunk)
        y, n = y_aug[..., :hd], y_aug[..., hd]

    y = y / jnp.maximum(jnp.abs(n[..., None]), 1.0)
    y = y.reshape(b, s, d_in).astype(x.dtype)
    y = rmsnorm(params["out_norm"], y, cfg.norm_eps) * jax.nn.silu(z)
    out = y @ params["w_down"]
    new_cache = None if cache is None else {"conv": new_conv, "H": new_H}
    return out, new_cache


def prefill_mlstm_cache(params, x, *, cfg):
    d_in, nh, hd = _mlstm_dims(cfg)
    b, s, _ = x.shape
    u, _ = _mlstm_qkvga(params, x, cfg)
    conv_state = jnp.pad(u, ((0, 0), (3, 0), (0, 0)))[:, -3:]
    cu, _ = _mamba_conv(u, params["conv_w"])
    q = (cu @ params["wq"]).reshape(b, s, nh, hd) * hd ** -0.5
    k = (cu @ params["wk"]).reshape(b, s, nh, hd) * hd ** -0.5
    v = (u @ params["wv"]).reshape(b, s, nh, hd)
    gates = u.astype(jnp.float32) @ params["w_gates"]
    i_g = jax.nn.sigmoid(gates[..., :nh])
    log_f = jax.nn.log_sigmoid(gates[..., nh:])
    _, H = chunked_gla(q, k, augment_value(v, i_g), log_f,
                       chunk=min(cfg.ssm.chunk, s))
    return {"conv": conv_state, "H": H}


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------
def init_slstm(key, cfg):
    d = cfg.d_model
    nh = cfg.n_heads
    hd = d // nh
    dt = dtype_of(cfg)
    k1, k2, k3 = split_key(key, 3)
    return {
        "w_gates": dense_init(k1, (d, 4 * d), jnp.float32),   # i,f,z,o (pre-head)
        "r_gates": dense_init(k2, (nh, hd, 4 * hd), jnp.float32) * 0.1,
        "w_out": dense_init(k3, (d, d), dt),
        "out_norm": {"scale": jnp.ones((d,), dt)},
    }


def _slstm_state0(b, d):
    return (jnp.zeros((b, d), jnp.float32), jnp.ones((b, d), jnp.float32),
            jnp.zeros((b, d), jnp.float32))


def slstm_cache_init(cfg, batch, dtype):
    return dict(zip("cnh", _slstm_state0(batch, cfg.d_model)))


def _on_tpu():
    return jax.default_backend() == "tpu"


def _use_kernel(s, hd):
    """The time recurrence runs as the Pallas kernel on TPU for more than
    one step at lane-aligned head widths; a decode step, and every other
    backend, keep the reference scan."""
    return s > 1 and hd % 128 == 0 and _on_tpu()


def _use_gla_kernel(s, chunk, hd):
    """The mLSTM's chunked GLA runs as the Pallas kernels on TPU over whole
    lane-aligned chunks at lane-aligned head widths; a ragged length, and
    every other backend, keep ``chunked_gla`` (the caller keeps it for a
    cache, decode and prefill alike)."""
    return (s > 1 and s % chunk == 0 and chunk % 128 == 0 and hd % 128 == 0
            and _on_tpu())


_ROWS, _WHOLE = "rows", "whole"


def _per_shard(fn, b, ins, outs):
    """``fn`` run on each device's rows of the mesh in context
    (``jax.set_mesh``): a kernel's custom call has no partitioning rule,
    and GSPMD would give every device the whole batch. ``ins`` and
    ``outs`` mirror ``fn``'s arguments and results, each leaf ``_ROWS``
    (split over the data-parallel axes that divide ``b``) or ``_WHOLE`` (a
    copy on every device). Without a mesh, ``fn`` itself."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return fn
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    dp = tuple(a for a in ("pod", "data") if a in sizes)
    rows = P(dp) if dp and b % math.prod(sizes[a] for a in dp) == 0 else P()
    spec = lambda tree: jax.tree.map(
        lambda leaf: rows if leaf == _ROWS else P(), tree)
    # a pallas_call's outputs carry no varying-axes type to check
    return jax.shard_map(fn, in_specs=spec(ins), out_specs=spec(outs),
                         check_vma=False)


def _slstm_scan(params, g_in, state):
    r = params["r_gates"]
    if not _use_kernel(g_in.shape[1], r.shape[1]):
        return slstm_scan_ref(g_in, r, state)
    from repro.kernels.slstm.ops import slstm_scan
    return _per_shard(slstm_scan, g_in.shape[0],
                      (_ROWS, _WHOLE, (_ROWS,) * 3),
                      (_ROWS, (_ROWS,) * 3))(g_in, r, state)


def apply_slstm(params, x, *, cfg, cache=None):
    b, s, d = x.shape
    g_in = x.astype(jnp.float32) @ params["w_gates"]        # (b,s,4d)
    state = _slstm_state0(b, d) if cache is None else \
        (cache["c"], cache["n"], cache["h"])
    hs, state = _slstm_scan(params, g_in, state)
    y = rmsnorm(params["out_norm"], hs.astype(x.dtype), cfg.norm_eps)
    out = y @ params["w_out"]
    return out, None if cache is None else dict(zip("cnh", state))


def prefill_slstm_cache(params, x, *, cfg):
    b, s, d = x.shape
    g_in = x.astype(jnp.float32) @ params["w_gates"]
    _, state = _slstm_scan(params, g_in, _slstm_state0(b, d))
    return dict(zip("cnh", state))
