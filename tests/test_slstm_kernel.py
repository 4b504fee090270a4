"""The sLSTM time-recurrence kernel against its ``lax.scan`` oracle, in
interpret mode: outputs, final state and gradients (g_in, R, initial
state) over ragged time blocks and row counts that are not a multiple of
8, with the input gate's clip active; the chip's packed bfloat16 R
against bfloat16 products; then the whole sLSTM block through
``apply_slstm``, kernel path against the scan path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SLSTM, ModelConfig, Segment
from repro.kernels.slstm import ops
from repro.kernels.slstm.ops import slstm_scan
from repro.kernels.slstm.ref import I_CLIP, slstm_scan_ref, slstm_step_ref
from repro.kernels.slstm.slstm import time_block
from repro.models import xlstm

# (b, s, d, nh): time blocks of 32 + 13; 8 + 8 + 4 over 12 rows; two
# whole blocks of 8; one block of 7
SHAPES = [(3, 45, 32, 4), (12, 20, 64, 2), (16, 16, 32, 4), (2, 7, 16, 1)]


def _inputs(b, s, d, nh, seed, g_scale=2.0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    hd = d // nh
    g = jax.random.normal(k[0], (b, s, 4 * d)) * g_scale
    r = jax.random.normal(k[1], (nh, hd, 4 * hd)) * 0.5
    state = (jax.random.normal(k[2], (b, d)),
             1.0 + jax.random.uniform(k[3], (b, d)),
             jax.random.normal(k[4], (b, d)))
    return g, r, state


def _loss(scan, w):
    def loss(g, r, state):
        hs, (c, n, h) = scan(g, r, state)
        return (jnp.sum(hs * w) + 0.3 * jnp.sum(c) + 0.1 * jnp.sum(n)
                + 0.7 * jnp.sum(h))
    return loss


def _close(a, b, rtol):
    a, b = np.asarray(a), np.asarray(b)
    scale = max(float(np.max(np.abs(b))), 1.0)
    np.testing.assert_allclose(a, b, rtol=0, atol=rtol * scale)


def test_time_blocks_cover_the_shapes():
    """The parity shapes cross block edges as their comment says."""
    assert [(time_block(b, s), s % time_block(b, s))
            for b, s, _, _ in SHAPES] == [(32, 13), (8, 4), (8, 0), (7, 0)]


@pytest.mark.parametrize("b,s,d,nh", SHAPES)
def test_slstm_scan_matches_ref(b, s, d, nh):
    g, r, state = _inputs(b, s, d, nh, seed=b * s)
    hs, final = slstm_scan(g, r, state)
    hs_ref, final_ref = slstm_scan_ref(g, r, state)
    _close(hs, hs_ref, 1e-5)
    for x, y in zip(final, final_ref):
        _close(x, y, 1e-5)


@pytest.mark.parametrize("b,s,d,nh", SHAPES)
def test_slstm_scan_grads_match_ref(b, s, d, nh):
    g, r, state = _inputs(b, s, d, nh, seed=b + s)
    w = jax.random.normal(jax.random.PRNGKey(7), (b, s, d))
    grads = jax.grad(_loss(slstm_scan, w), argnums=(0, 1, 2))(g, r, state)
    grads_ref = jax.grad(_loss(slstm_scan_ref, w), argnums=(0, 1, 2))(
        g, r, state)
    for x, y in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_ref)):
        assert x.shape == y.shape
        _close(x, y, 1e-4)


def test_slstm_scan_clip_active():
    """Input-gate pre-activations above the clip, and exactly on it (the
    first step, where h_0 = 0 adds nothing): JAX's min passes half the
    gradient at a tie, and so must the kernel."""
    b, s, d, nh = 3, 45, 32, 4
    g, r, _ = _inputs(b, s, d, nh, seed=3, g_scale=6.0)
    g = g.at[:, 0, :d // 2].set(I_CLIP)
    state = (jnp.zeros((b, d)), jnp.ones((b, d)), jnp.zeros((b, d)))
    assert float(jnp.mean(g[..., :d] > I_CLIP)) > 0.02
    w = jax.random.normal(jax.random.PRNGKey(8), (b, s, d))
    hs, _ = slstm_scan(g, r, state)
    hs_ref, _ = slstm_scan_ref(g, r, state)
    _close(hs, hs_ref, 1e-5)
    grads = jax.grad(_loss(slstm_scan, w), argnums=(0, 1))(g, r, state)
    grads_ref = jax.grad(_loss(slstm_scan_ref, w), argnums=(0, 1))(
        g, r, state)
    for x, y in zip(grads, grads_ref):
        _close(x, y, 1e-4)


def _bf16(x):
    """x rounded to bfloat16, with the gradient of the identity."""
    return x + jax.lax.stop_gradient(
        x.astype(jnp.bfloat16).astype(jnp.float32) - x)


def _bf16_products_ref(g_in, r_gates, state0):
    """The oracle with the recurrent products' operands rounded to
    bfloat16, as the chip's operand path rounds them."""
    r = _bf16(r_gates)

    def step(st, g_t):
        c, n, h = st
        return slstm_step_ref((c, n, _bf16(h)), g_t, r)
    state, hs = jax.lax.scan(step, state0, g_in.transpose(1, 0, 2))
    return hs.transpose(1, 0, 2), state


@pytest.mark.parametrize("b,s,d,nh", [(3, 45, 256, 2), (12, 20, 128, 1)])
def test_packed_bf16_weights_match_bf16_products(b, s, d, nh, monkeypatch):
    """The chip's operand path in interpret mode: R in bfloat16, packed
    two rows to a uint32 word in VMEM, through the forward and the
    backward, against the oracle with the same operand rounding. The
    backward rounds dg to bfloat16 where the oracle's does not, hence the
    gradients' wider tolerance (measured 2.2e-3); a misread packing is
    off by the whole signal."""
    monkeypatch.setattr(ops, "_operand",
                        lambda r, interpret: r.astype(jnp.bfloat16))
    g, r, state = _inputs(b, s, d, nh, seed=b * s)
    r = 0.2 * r
    got = slstm_scan(g, r, state)
    want = _bf16_products_ref(g, r, state)
    for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(x, y, 2e-5)
    w = jax.random.normal(jax.random.PRNGKey(7), (b, s, d))
    grads = jax.grad(_loss(slstm_scan, w), argnums=(0, 1, 2))(g, r, state)
    grads_ref = jax.grad(_loss(_bf16_products_ref, w), argnums=(0, 1, 2))(
        g, r, state)
    for x, y in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_ref)):
        _close(x, y, 1e-2)


def _slstm_cfg():
    return ModelConfig(name="slstm-t", family="ssm", d_model=256, n_heads=2,
                       n_kv_heads=2, d_ff=0, vocab_size=64,
                       segments=(Segment((SLSTM,), 1),), dtype="float32")


def test_apply_slstm_kernel_path_matches_scan_path(monkeypatch):
    """The whole block, loss and every parameter's gradient, with the
    recurrence on the kernel (interpret mode) against the scan it
    replaces. Head width 128, so the routing takes the kernel once it is
    told it is on the chip."""
    cfg = _slstm_cfg()
    params = xlstm.init_slstm(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 20, cfg.d_model))
    y = jax.random.normal(jax.random.PRNGKey(2), (3, 20, cfg.d_model))

    def loss(p):
        out, _ = xlstm.apply_slstm(p, x, cfg=cfg)
        return jnp.mean((out - y) ** 2)

    ref_loss, ref_grads = jax.value_and_grad(loss)(params)
    monkeypatch.setattr(xlstm, "_use_kernel", lambda s, hd: True)
    calls = []
    scan = ops.slstm_scan
    monkeypatch.setattr(ops, "slstm_scan",
                        lambda *a: calls.append(1) or scan(*a))
    k_loss, k_grads = jax.value_and_grad(loss)(params)
    assert calls
    np.testing.assert_allclose(float(k_loss), float(ref_loss), rtol=1e-5)
    for x_, y_ in zip(jax.tree.leaves(k_grads), jax.tree.leaves(ref_grads)):
        _close(x_, y_, 1e-4)


def test_kernel_routing(monkeypatch):
    """On the chip: the kernel for more than one step at lane-aligned
    heads; a decode step (s = 1) keeps the scan. Elsewhere: the scan."""
    assert not xlstm._use_kernel(2048, 256)
    monkeypatch.setattr(xlstm, "_on_tpu", lambda: True)
    assert xlstm._use_kernel(2048, 256)
    assert xlstm._use_kernel(2, 128)
    assert not xlstm._use_kernel(1, 256)
    assert not xlstm._use_kernel(2048, 64)
