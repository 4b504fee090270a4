"""The benchmark's float32 references against the program, on the CPU at
a small size: one period of each configuration's layer pattern, narrow
widths, float32 weights, the same weights and rows on both sides.

What is compared: the weighted training loss and every leaf of its
gradient, and each row's mean CE and Ĝ (the pool scores). The two sides
share no code; the program runs its chunked scans and blockwise
attention, the reference its quadratic forms, so they agree to float32
rounding of different summation orders.
"""
import copy
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from chipbench import harness, reference, traffic  # noqa: E402

SMALL = {
    "xlstm-350m": dict(d_model=64, n_heads=4, n_kv_heads=4, vocab_size=128,
                       ssm_chunk=16),
    "zamba2-1.2b": dict(d_model=64, n_heads=4, n_kv_heads=4, d_ff=96,
                        vocab_size=128, ssm_chunk=16, ssm_head_dim=16,
                        ssm_d_state=16),
}
T, B = 64, 3


def small_cfg(name, dtype="float32"):
    cfg = copy.deepcopy(json.loads(
        (HERE.parent / "configs" / f"{name}.json").read_text()))
    s = SMALL[name]
    for k, v in s.items():
        if k.startswith("ssm_"):
            cfg["ssm"][k[4:]] = v
        else:
            cfg[k] = v
    for seg in cfg["segments"]:
        seg["repeats"] = 1
    cfg["dtype"] = dtype
    return cfg


def rows(cfg, n, seed=3):
    tr = dict(traffic.load("is_on"), seq_len=T, states=32)
    return traffic.Rows(tr, cfg["vocab_size"], seed).gather(np.arange(n))


@pytest.fixture(scope="module", params=sorted(SMALL))
def case(request):
    cfg = small_cfg(request.param)
    from repro.models.lm import LM
    lm = LM(harness.model_config(cfg))
    params = reference.make_params(cfg, 11)
    return cfg, lm, params


def test_layout_matches_program(case):
    cfg, lm, _ = case
    want = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    have = jax.eval_shape(lambda: reference.make_params(cfg, 0))
    assert jax.tree_util.tree_structure(want) == \
        jax.tree_util.tree_structure(have)
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(have)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


@pytest.mark.parametrize("name", sorted(SMALL))
def test_layout_matches_program_at_cell_size(name):
    """The committed configuration as the cell runs it: every repeat, the
    published widths, the served dtype; shapes only."""
    from repro.models.lm import LM
    cfg = json.loads((HERE.parent / "configs" / f"{name}.json").read_text())
    want = jax.eval_shape(LM(harness.model_config(cfg)).init,
                          jax.random.PRNGKey(0))
    have = reference.param_shapes(cfg)
    assert jax.tree_util.tree_structure(want) == \
        jax.tree_util.tree_structure(have)
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(have)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_loss_and_gradient_match_program(case):
    cfg, lm, params = case
    data = rows(cfg, B)
    w = np.array([0.5, 1.25, 2.0])
    batch = {"tokens": jnp.asarray(data["tokens"]),
             "labels": jnp.asarray(data["labels"]),
             "weights": jnp.asarray(w, jnp.float32)}
    (lp, _), gp = jax.value_and_grad(
        lambda p: lm.loss(p, batch, remat=False), has_aux=True)(params)
    lr, gr = reference.loss_and_grad(params, data["tokens"], data["labels"],
                                     w, cfg)
    assert abs(float(lp) - lr) <= 1e-5 * abs(lr)
    flat_p = jax.tree_util.tree_flatten_with_path(gp)[0]
    flat_r = jax.tree_util.tree_leaves(gr)
    scale = max(float(jnp.max(jnp.abs(g))) for g in flat_r)
    for (path, a), b in zip(flat_p, flat_r):
        err = float(jnp.max(jnp.abs(a - b)))
        assert err <= 2e-4 * scale, (jax.tree_util.keystr(path), err, scale)


def test_pool_scores_match_program(case):
    cfg, lm, params = case
    data = rows(cfg, B + 2)
    batch = {"tokens": jnp.asarray(data["tokens"]),
             "labels": jnp.asarray(data["labels"])}
    loss_p, score_p = lm.sample_stats(params, batch, score_impl="naive")
    loss_r, score_r = reference.pool_stats(params, data["tokens"],
                                           data["labels"], cfg)
    np.testing.assert_allclose(np.asarray(loss_p), loss_r, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(score_p), score_r, rtol=1e-5)


def test_rows_depend_on_seed_and_id_only():
    tr = dict(traffic.load("is_on"), states=64)
    a = traffic.Rows(tr, 1000, 2 ** 31 + 5)
    b = traffic.Rows(tr, 1000, 2 ** 31 + 5)
    ids = np.array([7, 3, 12])
    ra = a.gather(ids)
    rb = b.gather(ids[::-1])
    np.testing.assert_array_equal(ra["tokens"], rb["tokens"][::-1])
    np.testing.assert_array_equal(ra["labels"][:, :-1], ra["tokens"][:, 1:])
    easy = [a.is_easy(i) for i in range(40)]
    for blk in range(4):
        assert sum(easy[blk * 10:(blk + 1) * 10]) == tr["easy_per_block"]
    probe = a.probe_ids()
    assert len(probe) == tr["probe_rows"] and probe[-1] < a.n
    assert sum(a.is_easy(i) for i in probe) == \
        tr["probe_rows"] // tr["block"] * tr["easy_per_block"]
