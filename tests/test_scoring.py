"""The decoupled scoring engine (repro.scoring) and the engine-backed
host-side presample path: engine == sample_stats, score_dtype behaviour,
out-of-band ScoreStore refresh, overlapped vs synchronous training, the
multi-host gather hook, and sharded execution when devices allow."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import (ISConfig, OptimConfig, RunConfig,
                                SamplerConfig, ShapeConfig)
from repro.data.pipeline import PipelineState, SyntheticCLS, SyntheticLM
from repro.launch.mesh import build_mesh
from repro.models.lm import LM
from repro.api import Experiment as Trainer
from repro.scoring import ScoreEngine


def _run_cfg(cfg, *, host_score=False, overlap=True, tau_th=1.1,
             score_dtype="bfloat16", seq=16, batch=8, ratio=3):
    return RunConfig(
        model=cfg,
        shape=ShapeConfig("t", seq_len=seq, global_batch=batch, kind="train"),
        optim=OptimConfig(name="adamw", lr=1e-3, weight_decay=0.0),
        imp=ISConfig(enabled=True, presample_ratio=ratio, tau_th=tau_th,
                     score_dtype=score_dtype, overlap_scoring=overlap),
        sampler=SamplerConfig(scheme="presample", host_score=host_score),
        remat=False)


def _batch(cfg, n=8, seq=16, seed=0):
    rng = np.random.RandomState(seed)
    return {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (n, seq))),
            "labels": jnp.asarray(rng.randint(0, cfg.vocab_size, (n, seq)))}


# ---------------------------------------------------------------------------
# engine == sample_stats
# ---------------------------------------------------------------------------
def test_engine_matches_sample_stats_exactly_without_cast():
    cfg = get_config("lm-tiny")
    lm = LM(cfg)
    run = _run_cfg(cfg, score_dtype="none")
    eng = lm.score_engine(run)
    assert eng.score_dtype is None
    params = lm.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    loss_e, sc_e = eng.score_host(params, batch)
    loss_r, sc_r = lm.sample_stats(params, batch)
    # separate jit compilations fuse differently: last-ulp tolerance
    np.testing.assert_allclose(loss_e, np.asarray(loss_r), rtol=1e-6)
    np.testing.assert_allclose(sc_e, np.asarray(sc_r), rtol=1e-6)


def test_engine_score_dtype_ranks_like_f32():
    """bf16 scoring is for RANKING: scores approximate the f32 path and
    order candidates nearly identically."""
    cfg = get_config("lm-tiny")
    lm = LM(cfg)
    eng = ScoreEngine(lm, _run_cfg(cfg, score_dtype="bfloat16"))
    params = lm.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, n=16)
    _, sc16 = eng.score_host(params, batch)
    _, sc32 = lm.sample_stats(params, batch)
    sc32 = np.asarray(sc32)
    assert sc16.dtype == np.float32          # stats come back f32
    np.testing.assert_allclose(sc16, sc32, rtol=0.1)
    # Spearman-ish: top-half membership mostly agrees
    top16 = set(np.argsort(-sc16)[:8])
    top32 = set(np.argsort(-sc32)[:8])
    assert len(top16 & top32) >= 6


def test_engine_jit_cache_reused():
    cfg = get_config("lm-tiny")
    lm = LM(cfg)
    eng = ScoreEngine(lm, _run_cfg(cfg))
    params = lm.init(jax.random.PRNGKey(0))
    eng.score(params, _batch(cfg, seed=1))
    eng.score(params, _batch(cfg, seed=2))       # same shapes: one entry
    assert len(eng._jitted) == 1
    eng.score(params, _batch(cfg, n=4, seed=3))  # new shape: second entry
    assert len(eng._jitted) == 2


# ---------------------------------------------------------------------------
# out-of-band ScoreStore refresh
# ---------------------------------------------------------------------------
def test_refresh_scores_out_of_band():
    cfg = get_config("lm-tiny")
    run = _run_cfg(cfg)
    src = SyntheticLM(cfg.vocab_size, 16, n_examples=64, seed=5,
                      host_id=0, n_hosts=1)
    tr = Trainer(run, source=src)
    params = tr.lm.init(jax.random.PRNGKey(0))
    assert tr.sampler.store.coverage() == 0.0
    gids = np.arange(32)
    written = tr.sampler.refresh_scores(params, gids, epoch=0)
    assert written == 32
    assert tr.sampler.store.coverage() == pytest.approx(0.5)
    assert (tr.sampler.store.scores[tr.sampler.store.slot(gids)] > 0).all()


# ---------------------------------------------------------------------------
# host-side presample scheme (engine-backed Algorithm 1)
# ---------------------------------------------------------------------------
def test_host_presample_scores_all_candidates_into_store():
    cfg = get_config("lm-tiny")
    run = _run_cfg(cfg, host_score=True)
    src = SyntheticLM(cfg.vocab_size, 16, n_examples=48, seed=5,
                      host_id=0, n_hosts=1)
    tr = Trainer(run, source=src)
    assert tr.sampler.scheme == "presample_host"
    assert tr.sampler.uses_score_step
    tr.fit(steps=2)
    # 2 steps × B=24 candidates cover the whole 48-example set out-of-band
    assert tr.sampler.store.coverage() == pytest.approx(1.0)


def test_host_presample_activates_and_weights_unbiased():
    cfg = get_config("lm-tiny")
    run = _run_cfg(cfg, host_score=True, tau_th=1.0001)
    src = SyntheticCLS(cfg.vocab_size, 16, seed=4, host_id=0, n_hosts=1)
    tr = Trainer(run, source=src)
    state, hist = tr.fit(steps=30)
    assert all(np.isfinite(h["loss"]) for h in hist)
    assert any(h["is_active"] > 0 for h in hist)
    assert int(jax.device_get(state["step"])) == 30
    # spot-check the weighting identity on a fresh selection
    handle = tr.sampler.begin(PipelineState(), 99, params=state["params"])
    batch, meta, _ = tr.sampler.finish(handle)
    if meta["is_flag"] > 0:
        w = batch["weights"]
        assert w.shape == (run.shape.global_batch,)
        assert (w > 0).all() and np.isfinite(w).all()


def test_host_presample_overlap_matches_sync_convergence():
    """Overlap scores with one-step-stale params — selection differs, but
    training must stay in the same convergence regime as the sync path."""
    cfg = get_config("lm-tiny")
    losses = {}
    for overlap in (False, True):
        run = _run_cfg(cfg, host_score=True, overlap=overlap, tau_th=1.05)
        src = SyntheticCLS(cfg.vocab_size, 16, seed=4, host_id=0, n_hosts=1)
        tr = Trainer(run, source=src)
        _, hist = tr.fit(steps=30)
        losses[overlap] = float(np.mean([h["loss"] for h in hist[-5:]]))
    assert np.isfinite(losses[False]) and np.isfinite(losses[True])
    assert losses[True] < losses[False] * 3 + 1.0


def test_host_presample_checkpoint_roundtrip(tmp_path):
    cfg = get_config("lm-tiny")
    import dataclasses
    run = dataclasses.replace(_run_cfg(cfg, host_score=True),
                              ckpt_dir=str(tmp_path), ckpt_every=4)
    src = SyntheticLM(cfg.vocab_size, 16, n_examples=64, seed=5,
                      host_id=0, n_hosts=1)
    tr = Trainer(run, source=src)
    tr.fit(steps=4)
    tr2 = Trainer(run, source=src)
    state, pstate, step = tr2.resume_or_init()
    assert step == 4
    assert tr2.sampler.store.coverage() > 0
    assert float(tr2.sampler.tau_ema) == pytest.approx(
        float(tr.sampler.tau_ema))


# ---------------------------------------------------------------------------
# fallback + gather hook
# ---------------------------------------------------------------------------
def test_host_presample_kill_switch_falls_back_to_uniform():
    import dataclasses
    cfg = get_config("lm-tiny")
    run = _run_cfg(cfg, host_score=True)
    run = dataclasses.replace(run, imp=dataclasses.replace(run.imp,
                                                           enabled=False))
    tr = Trainer(run)
    assert tr.sampler.scheme == "uniform"


def test_gather_scores_single_host_identity_and_interleave():
    cfg = get_config("lm-tiny")
    eng = ScoreEngine(LM(cfg), _run_cfg(cfg))
    local = np.asarray([3.0, 1.0, 2.0], np.float32)
    out = eng.gather_scores(local)
    np.testing.assert_array_equal(out, local)
    # the strided interleave rule itself (simulated 2-host reassembly)
    from repro.distributed.collectives import gather_host_scores
    full = np.arange(6, dtype=np.float32)
    shards = [full[h::2] for h in range(2)]
    rebuilt = np.full((6,), -1.0, np.float32)
    for h, sh in enumerate(shards):
        rebuilt[h::2] = sh
    np.testing.assert_array_equal(rebuilt, full)
    # single-process call with explicit n_global trims padding
    np.testing.assert_array_equal(
        gather_host_scores(full, n_hosts=1, n_global=4), full[:4])


# ---------------------------------------------------------------------------
# sharded engine (exercised under the multi-device CI variant)
# ---------------------------------------------------------------------------
@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >1 device (CI runs an 8-device variant)")
def test_engine_sharded_matches_single_device():
    cfg = get_config("lm-tiny")
    lm = LM(cfg)
    run = _run_cfg(cfg, score_dtype="none")
    params = lm.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, n=len(jax.devices()) * 2)
    ref_loss, ref_sc = ScoreEngine(lm, run).score_host(params, batch)
    mesh = build_mesh((len(jax.devices()),), ("data",))
    eng = ScoreEngine(lm, run, mesh=mesh)
    loss, sc = eng.score_host(params, batch)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(sc, ref_sc, rtol=1e-5, atol=1e-5)
