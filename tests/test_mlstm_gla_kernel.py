"""The mLSTM's gated-linear-attention kernels against their oracle, in
interpret mode: y and the normaliser, and the VJP in q, k, v, the input
gate and the log forget gate, over several (b, s, chunk) and head widths
of 128 and 256, with strongly decaying and near-1 forget gates and an
input gate near 0; the chip's bfloat16 operands against the float32
oracle; the whole mLSTM block, kernel path against ``chunked_gla``'s; the
routing; and a (2, 2) CPU mesh through ``shard_map``."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MLSTM, ModelConfig, Segment
from repro.kernels.mlstm_gla import ops
from repro.kernels.mlstm_gla.ops import mlstm_gla
from repro.kernels.mlstm_gla.ref import mlstm_gla_ref
from repro.models import xlstm
from repro.models.ssm import gla_scan_ref

ROOT = Path(__file__).resolve().parents[1]

# (b, s, h, hd, chunk): 2 chunks of two 128-row sub-tiles; 3 chunks of
# one; one chunk of 256 at hd 256; 4 chunks of 32, one sub-tile each
SHAPES = [(2, 512, 2, 128, 256), (3, 384, 1, 128, 128),
          (1, 256, 2, 256, 256), (2, 128, 1, 256, 32)]
# the forget gate's pre-activation offset: log f ≈ −7 (f ≈ 1e-3),
# about −0.7, and ≈ −1e-4 (f ≈ 1)
GATES = {"decaying": -7.0, "mid": 0.0, "near_one": 9.0}


def _inputs(b, s, h, hd, seed, f_offset=0.0, i_offset=0.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, s, h, hd)) * hd ** -0.5
    k = jax.random.normal(ks[1], (b, s, h, hd)) * hd ** -0.5
    v = jax.random.normal(ks[2], (b, s, h, hd))
    i_gate = jax.nn.sigmoid(jax.random.normal(ks[3], (b, s, h)) + i_offset)
    log_f = jax.nn.log_sigmoid(jax.random.normal(ks[4], (b, s, h))
                               + f_offset)
    return q, k, v, i_gate, log_f


def _loss(op, chunk, wy, wn):
    def loss(*args):
        y, n = op(*args, chunk)
        return jnp.sum(y * wy) + jnp.sum(n * wn)
    return loss


def _close(a, b, rtol):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    scale = max(float(np.max(np.abs(b))), 1e-30)
    np.testing.assert_allclose(a, b, rtol=0, atol=rtol * scale)


def _check(args, chunk, rtol_fwd, rtol_grad, op=mlstm_gla):
    y, n = op(*args, chunk)
    y_ref, n_ref = mlstm_gla_ref(*args, chunk)
    assert y.shape == y_ref.shape and n.shape == n_ref.shape
    _close(y, y_ref, rtol_fwd)
    _close(n, n_ref, rtol_fwd)
    kw = jax.random.split(jax.random.PRNGKey(99))
    wy = jax.random.normal(kw[0], y.shape)
    wn = jax.random.normal(kw[1], n.shape)
    grads = jax.grad(_loss(op, chunk, wy, wn), argnums=range(5))(*args)
    grads_ref = jax.grad(_loss(mlstm_gla_ref, chunk, wy, wn),
                         argnums=range(5))(*args)
    for name, g, g_ref in zip(("q", "k", "v", "i_gate", "log_f"), grads,
                              grads_ref):
        assert g.shape == g_ref.shape and g.dtype == g_ref.dtype, name
        _close(g, g_ref, rtol_grad)


@pytest.mark.parametrize("b,s,h,hd,chunk", SHAPES)
def test_mlstm_gla_matches_ref(b, s, h, hd, chunk):
    _check(_inputs(b, s, h, hd, seed=b * s + hd), chunk, 1e-5, 1e-4)


@pytest.mark.parametrize("gate", sorted(GATES))
def test_mlstm_gla_forget_gate_regimes(gate):
    """Strongly decaying gates (the state forgets within a few steps,
    c_Q ≈ −1800 over a chunk) and gates near 1 (it forgets nothing)."""
    args = _inputs(2, 512, 1, 128, seed=5, f_offset=GATES[gate])
    _check(args, 256, 1e-5, 1e-4)


def test_mlstm_gla_input_gate_near_zero():
    """i ≈ 1e-6: the normaliser stays far below 1, and y with it."""
    args = _inputs(1, 512, 2, 128, seed=6, i_offset=-14.0)
    assert float(jnp.max(args[3])) < 1e-4
    _check(args, 256, 1e-5, 1e-4)


def test_mlstm_gla_matches_sequential_oracle():
    """Against the step-by-step recurrence, which shares no code with
    ``chunked_gla``: y, and the normaliser as the last value column."""
    q, k, v, i_gate, log_f = _inputs(1, 256, 2, 128, seed=7)
    y, n = mlstm_gla(q, k, v, i_gate, log_f, 128)
    v_aug = jnp.concatenate([v * i_gate[..., None], i_gate[..., None]], -1)
    y_seq, _ = gla_scan_ref(q, k, v_aug, log_f)
    _close(y, y_seq[..., :-1], 1e-5)
    _close(n, y_seq[..., -1], 1e-5)


def test_bf16_operands_match_the_float32_oracle(monkeypatch):
    """The chip's operand path in interpret mode: every product's
    operands in bfloat16, q, k and v in bfloat16 as the model gives them,
    the states saved for the backward in bfloat16. Against the float32
    oracle on the same bfloat16 inputs it is off by bfloat16 rounding
    (measured up to 4.9e-3 of the largest entry, y and each gradient); a
    misplaced tile or state is off by the whole signal."""
    monkeypatch.setattr(ops, "_mxu", lambda interpret: jnp.bfloat16)
    q, k, v, i_gate, log_f = _inputs(2, 512, 2, 128, seed=8)
    args = tuple(x.astype(jnp.bfloat16) for x in (q, k, v)) + (i_gate,
                                                                log_f)
    ref = lambda *a: mlstm_gla_ref(*(x.astype(jnp.float32) for x in a[:3]),
                                   *a[3:])
    y, n = mlstm_gla(*args, 256)
    y_ref, n_ref = ref(*args, 256)
    assert y.dtype == jnp.bfloat16 and n.dtype == jnp.float32
    _close(y, y_ref, 1e-2)
    _close(n, n_ref, 1e-2)
    kw = jax.random.split(jax.random.PRNGKey(3))
    wy = jax.random.normal(kw[0], y.shape)
    wn = jax.random.normal(kw[1], n.shape)
    grads = jax.grad(_loss(mlstm_gla, 256, wy, wn), argnums=range(5))(*args)
    grads_ref = jax.grad(_loss(ref, 256, wy, wn), argnums=range(5))(*args)
    for g, g_ref in zip(grads, grads_ref):
        assert g.dtype == g_ref.dtype
        _close(g, g_ref, 1e-2)


def _mlstm_cfg(d_model=256, n_heads=4):
    return ModelConfig(name="mlstm-t", family="ssm", d_model=d_model,
                       n_heads=n_heads, n_kv_heads=n_heads, d_ff=0,
                       vocab_size=64, segments=(Segment((MLSTM,), 1),),
                       dtype="float32")


def test_apply_mlstm_kernel_path_matches_gla_path(monkeypatch):
    """The whole block, loss and every parameter's gradient, with the
    mixing on the kernels (interpret mode) against ``chunked_gla``. Four
    heads of 128 over two chunks, so the routing takes the kernels once it
    is told it is on the chip."""
    cfg = _mlstm_cfg()
    params = xlstm.init_mlstm(jax.random.PRNGKey(0), cfg)
    s = 2 * cfg.ssm.chunk
    x = jax.random.normal(jax.random.PRNGKey(1), (2, s, cfg.d_model))
    y = jax.random.normal(jax.random.PRNGKey(2), (2, s, cfg.d_model))

    def loss(p):
        out, _ = xlstm.apply_mlstm(p, x, cfg=cfg)
        return jnp.mean((out - y) ** 2)

    ref_loss, ref_grads = jax.value_and_grad(loss)(params)
    monkeypatch.setattr(xlstm, "_on_tpu", lambda: True)
    calls = []
    op = ops.mlstm_gla
    monkeypatch.setattr(ops, "mlstm_gla",
                        lambda *a, **kw: calls.append(1) or op(*a, **kw))
    k_loss, k_grads = jax.value_and_grad(loss)(params)
    assert calls
    np.testing.assert_allclose(float(k_loss), float(ref_loss), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(k_grads), jax.tree.leaves(ref_grads)):
        _close(a, b, 1e-4)


def test_gla_kernel_routing(monkeypatch):
    """On the chip: the kernels over whole 128-aligned chunks at
    lane-aligned heads. A ragged length, a chunk that is not a multiple
    of 128, a head width of 64, one step, or another backend keep
    ``chunked_gla``."""
    assert not xlstm._use_gla_kernel(2048, 256, 512)
    monkeypatch.setattr(xlstm, "_on_tpu", lambda: True)
    assert xlstm._use_gla_kernel(2048, 256, 512)
    assert xlstm._use_gla_kernel(256, 256, 128)
    assert not xlstm._use_gla_kernel(2000, 256, 512)     # ragged
    assert not xlstm._use_gla_kernel(2048, 64, 512)      # chunk
    assert not xlstm._use_gla_kernel(2048, 256, 64)      # head width
    assert not xlstm._use_gla_kernel(1, 1, 512)          # one step


@pytest.mark.parametrize("case", ["decode", "cache_prefill", "ragged",
                                  "head_64"])
def test_apply_mlstm_keeps_chunked_gla(case, monkeypatch):
    """On the chip, these calls of the block never reach the kernels:
    decode and prefill into a cache carry a state, a ragged length is not
    whole chunks, and a head of 64 is not lane-aligned."""
    monkeypatch.setattr(xlstm, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "mlstm_gla", lambda *a, **kw: pytest.fail(
        "the kernel was called"))
    cfg = _mlstm_cfg(d_model=128 if case == "head_64" else 256)
    params = xlstm.init_mlstm(jax.random.PRNGKey(0), cfg)
    s = {"decode": 1, "cache_prefill": 256, "ragged": 300,
         "head_64": 256}[case]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, s, cfg.d_model))
    cache = (xlstm.mlstm_cache_init(cfg, 2, jnp.float32)
             if case in ("decode", "cache_prefill") else None)
    out, new_cache = xlstm.apply_mlstm(params, x, cfg=cfg, cache=cache)
    assert out.shape == x.shape and bool(jnp.all(jnp.isfinite(out)))
    assert (new_cache is None) == (cache is None)


def test_mlstm_gla_kernel_on_a_cpu_mesh():
    """The block on a (data 2, model 2) CPU mesh (``tests/mp_checks.py``,
    in a subprocess with a forced device count) with the mixing on the
    kernels in ``shard_map`` equals ``chunked_gla`` on one device, loss
    and every gradient; each kernel call sees one data shard's rows."""
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    r = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "mp_checks.py"),
         "mlstm_gla_kernel_sharded"],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr[-3000:]}"
    assert "OK mlstm_gla_kernel_sharded" in r.stdout
