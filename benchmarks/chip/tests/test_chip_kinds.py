"""The reference's block-kind contract for a kind that holds weights both
once per segment and per repeat and reads the input embeddings
(``toy_kind.py``), composed with a plain kind (sLSTM) in one segment and
alone in another, against a hand-unrolled float32 loop over the layers:
the layout of its weights, the hidden states, the pool scores, the loss
and its gradient (the shared weights' summed over their invocations),
the FLOPs of each invocation, and the same on a (data 2, model 2) mesh
of forced CPU devices (``kinds_mesh_check.py``, in a subprocess).
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import print_saved_residuals

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[2]
sys.path[:0] = [str(HERE), str(HERE.parent)]

import toy_kind  # noqa: E402
from chipbench import reference  # noqa: E402
from chipbench.numerics import HIGHEST, Numerics  # noqa: E402
from chipbench.refs import slstm  # noqa: E402

TOY = toy_kind.KIND
CFG = {"name": "toy", "d_model": 8, "n_heads": 2, "n_kv_heads": 2,
       "head_dim": 0, "d_ff": 12, "vocab_size": 16, "tie_embeddings": True,
       "norm_eps": 1e-5, "rope_theta": 10000.0, "dtype": "float32",
       "segments": [{"pattern": [TOY, "slstm", TOY], "repeats": 3},
                    {"pattern": [TOY], "repeats": 2}]}
T, ROWS, SEED = 8, 6, 2 ** 31 + 19


@pytest.fixture(autouse=True)
def toy(monkeypatch):
    monkeypatch.setitem(sys.modules, toy_kind.MODULE, toy_kind)


def data(seed=5):
    r = np.random.default_rng(seed)
    tokens = r.integers(0, CFG["vocab_size"], (ROWS, T)).astype(np.int32)
    labels = r.integers(0, CFG["vocab_size"], (ROWS, T)).astype(np.int32)
    weights = r.uniform(0.5, 2.0, ROWS)
    return tokens, labels, weights


def toy_invocations(cfg):
    """(segment, repeat, position) of every invocation of the toy kind."""
    return [(i, r, pi) for i, seg in enumerate(cfg["segments"])
            for r in range(seg["repeats"])
            for pi, kind in enumerate(seg["pattern"]) if kind == TOY]


def unrolled_stats(params, tokens, labels, cfg, copies=None):
    """Per-row (mean CE, Ĝ) by a loop over every layer in turn. With
    ``copies`` ({invocation: S}), each invocation of the toy kind reads
    its own copy of its segment's shared S."""
    num, hp = Numerics("f32"), HIGHEST
    x0 = x = params["embed"][tokens]
    for i, seg in enumerate(cfg["segments"]):
        sp = params["segments"][f"seg{i}"]
        for r in range(seg["repeats"]):
            for pi, kind in enumerate(seg["pattern"]):
                p = jax.tree_util.tree_map(lambda a: a[r],
                                           sp["stacked"][f"p{pi}"])
                if kind == TOY:
                    s = sp["shared"]["s"] if copies is None \
                        else copies[(i, r, pi)]
                    h = jnp.tanh(jnp.matmul(jnp.concatenate([x, x0], -1), s,
                                            precision=hp))
                    x = x + jnp.matmul(h, p["w"], precision=hp)
                else:
                    x = slstm.apply(p, x, cfg, num)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                          + cfg["norm_eps"]) * params["final_norm"]["scale"]
    logp = jax.nn.log_softmax(jnp.matmul(x, params["embed"].T, precision=hp))
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1)[..., 0],
                   axis=-1)
    err = jnp.exp(logp) - jax.nn.one_hot(labels, cfg["vocab_size"])
    return ce, jnp.sqrt(jnp.sum(err * err, axis=(1, 2)))


@pytest.fixture(scope="module")
def params():
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(sys.modules, toy_kind.MODULE, toy_kind)
        return reference.make_params(CFG, SEED)


def test_layout_holds_shared_once_and_per_repeat_stacked(params):
    d = CFG["d_model"]
    spec = reference.param_spec(CFG)
    for i, seg in enumerate(CFG["segments"]):
        s = spec["segments"][f"seg{i}"]
        assert s["shared"] == toy_kind.shared_spec(CFG)
        for pi, kind in enumerate(seg["pattern"]):
            if kind == TOY:
                (shape, dt, init), = toy_kind.spec(CFG).values()
                assert s["stacked"][f"p{pi}"] == {
                    "w": ((seg["repeats"],) + shape, dt, init)}
        sp = params["segments"][f"seg{i}"]
        assert sp["shared"]["s"].shape == (2 * d, d)
        assert sp["stacked"]["p0"]["w"].shape == (seg["repeats"], d, d)
    assert params["segments"]["seg0"]["stacked"]["p1"]["inner"][
        "w_gates"].shape == (3, d, 4 * d)


class _Both:
    KIND = "both"
    SHARED = True

    @staticmethod
    def spec(cfg):
        return {}

    @staticmethod
    def shared_spec(cfg):
        return {}


def test_two_owners_of_shared_weights_is_an_error(monkeypatch):
    cfg = dict(CFG, segments=[{"pattern": [TOY, "shared_attn"],
                               "repeats": 2}])
    with pytest.raises(ValueError, match="both hold shared weights"):
        reference.param_spec(cfg)
    monkeypatch.setitem(sys.modules, "chipbench.refs.both", _Both)
    with pytest.raises(ValueError, match="both SHARED and shared_spec"):
        reference.param_spec(dict(CFG, segments=[{"pattern": ["both"],
                                                  "repeats": 1}]))


def test_hidden_and_pool_scores_match_unrolled_loop(params):
    tokens, labels, _ = data()
    ce, sc = reference.pool_stats(params, tokens, labels, CFG)
    ce_u, sc_u = unrolled_stats(params, jnp.asarray(tokens),
                                jnp.asarray(labels), CFG)
    np.testing.assert_allclose(ce, np.asarray(ce_u), rtol=1e-6)
    np.testing.assert_allclose(sc, np.asarray(sc_u), rtol=1e-6)


def test_loss_and_gradient_match_unrolled_loop(params):
    """The shared S's gradient is the sum of what each invocation's own
    copy of S receives."""
    tokens, labels, weights = data()
    loss, grad = reference.loss_and_grad(params, tokens, labels, weights,
                                         CFG)
    inv = toy_invocations(CFG)
    copies = {k: params["segments"][f"seg{k[0]}"]["shared"]["s"]
              for k in inv}

    def f(p, c):
        ce, _ = unrolled_stats(p, jnp.asarray(tokens), jnp.asarray(labels),
                               CFG, c)
        return jnp.sum(ce * jnp.asarray(weights, jnp.float32)) / ROWS
    loss_u, (g_p, g_c) = jax.value_and_grad(f, argnums=(0, 1))(params,
                                                               copies)
    assert abs(loss - float(loss_u)) <= 1e-6 * abs(float(loss_u))
    for i in range(len(CFG["segments"])):
        got = grad["segments"][f"seg{i}"]["shared"]["s"]
        parts = [g_c[k] for k in inv if k[0] == i]
        seg = CFG["segments"][i]
        assert len(parts) == seg["pattern"].count(TOY) * seg["repeats"]
        np.testing.assert_allclose(got, sum(parts), rtol=1e-5,
                                   atol=1e-6 * float(jnp.max(jnp.abs(got))))
        g_p["segments"][f"seg{i}"]["shared"]["s"] = got
    flat_u = jax.tree_util.tree_flatten_with_path(g_p)[0]
    for (path, a), b in zip(flat_u, jax.tree_util.tree_leaves(grad)):
        scale = float(jnp.max(jnp.abs(a)))
        assert scale > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-6 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def test_input_embeddings_add_no_residual_per_repeat(params, capsys):
    """Under the segment scan's checkpoint the only residual stacked per
    repeat at the stream's shape is the stream itself; x0 is held once."""
    tokens, labels, weights = data()
    num = Numerics("f32")
    print_saved_residuals(
        lambda p: reference._loss(p, jnp.asarray(tokens), jnp.asarray(labels),
                                  jnp.asarray(weights, jnp.float32), CFG,
                                  num)[0], params)
    out = capsys.readouterr().out
    d = CFG["d_model"]
    for seg in CFG["segments"]:
        stacked = f"f32[{seg['repeats']},{ROWS},{T},{d}]"
        assert out.count(stacked) == 1, out


def test_flops_count_every_invocation():
    d, V = CFG["d_model"], CFG["vocab_size"]
    toy = 2 * T * (2 * d * d + d * d)                  # 3072
    # sLSTM at d 8, 2 heads, 8 tokens: test_chip_flops.py's hand count
    want = 2 * T * d * V + 3 * (2 * toy + 7168) + 2 * toy
    assert reference.flops_per_row(CFG, T) == want == 48128


def test_on_a_mesh_matches_unplaced():
    """Loss, gradient and pool scores laid out over a (2, 2) mesh of
    forced CPU devices equal the unplaced reference's, and the shared
    and per-repeat leaves lie over all four devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [str(REPO / "src"), str(HERE.parent)]))
    p = subprocess.run([sys.executable, str(HERE / "kinds_mesh_check.py")],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    assert out["loss_rel_gap"] <= 1e-6, out
    assert out["grad_rel_gap"] <= 1e-5, out
    assert out["score_rel_gap"] <= 1e-6, out
    for part in ("params", "grad"):
        layout = out["layout"][part]
        assert layout and all(n == 4 for n, _ in layout.values()), layout
        for leaf in ("['shared']['s']", "['stacked']['p0']['w']"):
            split = [not whole for path, (_, whole) in layout.items()
                     if leaf in path]
            assert split and all(split), (leaf, layout)
