"""Multi-device checks, run in a subprocess with a forced 8-device host
platform (tests/test_distributed.py drives this). Each check prints OK or
raises."""
import os
import sys

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.launch.mesh import build_mesh  # noqa: E402


def check_pipeline_parallel():
    """GPipe over 4 stages == sequential application."""
    from repro.distributed.pipeline_parallel import pipeline_forward

    n_stage, M, mb, d = 4, 6, 2, 8
    mesh = build_mesh((n_stage,), ("pod",))
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(n_stage, d, d).astype(np.float32) * 0.3)
    x = jnp.asarray(rng.randn(M, mb, d).astype(np.float32))

    def seq(x):
        for i in range(n_stage):
            x = jnp.tanh(x @ w[i])
        return x

    expected = jax.vmap(seq)(x)

    def staged(wi, m):
        return pipeline_forward(
            wi[0], m, lambda a: jnp.tanh(a @ wi[0]), "pod")

    out = jax.jit(jax.shard_map(staged, mesh=mesh, in_specs=(P("pod"), P()),
                            out_specs=P(), check_vma=False))(w, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)
    print("OK pipeline_parallel")


def check_sharded_is_step_matches_single_device():
    """The IS train step under a (4,2) mesh == single-device execution."""
    from repro.configs import get_config
    from repro.configs.base import ISConfig, OptimConfig, RunConfig, ShapeConfig
    from repro.core.is_train import build_train_step, train_state_init
    from repro.distributed import sharding as shd
    from repro.models.lm import LM
    from repro.optim.api import get_optimizer

    cfg = get_config("lm-tiny")
    shape = ShapeConfig("t", seq_len=16, global_batch=8, kind="train")
    run = RunConfig(model=cfg, shape=shape,
                    optim=OptimConfig(name="sgd", lr=0.1),
                    imp=ISConfig(enabled=True, presample_ratio=3),
                    remat=False)
    lm = LM(cfg)
    opt = get_optimizer(run.optim)
    step = build_train_step(lm, run, opt, gate="always")
    key = jax.random.PRNGKey(0)
    state = train_state_init(lm, opt, key)
    rng = np.random.RandomState(0)
    batch = {
        "tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (24, 16))),
        "labels": jnp.asarray(rng.randint(0, cfg.vocab_size, (24, 16))),
    }
    # single device
    s1, m1 = jax.jit(step)(state, batch)

    # sharded
    mesh = build_mesh((4, 2), ("data", "model"))
    state_sds = jax.eval_shape(lambda k: train_state_init(lm, opt, k), key)
    sspecs = shd.state_specs(cfg, state_sds, mesh)
    named = lambda t: shd.to_named(t, mesh)
    state2 = train_state_init(lm, opt, key)
    with jax.set_mesh(mesh):
        fn = jax.jit(step, in_shardings=(named(sspecs), named(
            shd.batch_specs(cfg, jax.eval_shape(lambda: batch), mesh))),
            out_shardings=(named(sspecs), None))
        s2, m2 = fn(state2, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3, \
        (float(m1["loss"]), float(m2["loss"]))
    la = jax.tree_util.tree_leaves(s1["params"])
    lb = jax.tree_util.tree_leaves(s2["params"])
    for a, b in zip(la, lb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(jax.device_get(b)),
                                   rtol=5e-3, atol=5e-3)
    print("OK sharded_is_step")


def check_score_engine_sharded():
    """The decoupled scoring engine under a (4,2) mesh == single-device
    scores (batch-only sharding; params ride along replicated)."""
    from repro.configs import get_config
    from repro.configs.base import ISConfig, OptimConfig, RunConfig, ShapeConfig
    from repro.models.lm import LM
    from repro.scoring import ScoreEngine

    cfg = get_config("lm-tiny")
    run = RunConfig(model=cfg,
                    shape=ShapeConfig("t", seq_len=16, global_batch=8,
                                      kind="train"),
                    optim=OptimConfig(name="sgd", lr=0.1),
                    imp=ISConfig(enabled=True, presample_ratio=3,
                                 score_dtype="none"),
                    remat=False)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    batch = {
        "tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (24, 16))),
        "labels": jnp.asarray(rng.randint(0, cfg.vocab_size, (24, 16))),
    }
    ref_loss, ref_sc = ScoreEngine(lm, run).score_host(params, batch)

    mesh = build_mesh((4, 2), ("data", "model"))
    eng = ScoreEngine(lm, run, mesh=mesh)
    with jax.set_mesh(mesh):
        loss, sc = eng.score_host(params, batch)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(sc, ref_sc, rtol=1e-4, atol=1e-5)
    print("OK score_engine_sharded")


def check_slstm_kernel_sharded():
    """The sLSTM block under a (4,2) mesh with the recurrence on the
    kernel (interpret mode) == the scan on one device, loss and every
    parameter's gradient; the kernel sees each data shard's rows."""
    from repro.configs.base import SLSTM, ModelConfig, Segment
    from repro.distributed import sharding as shd
    from repro.kernels.slstm import ops
    from repro.models import xlstm

    cfg = ModelConfig(name="slstm-t", family="ssm", d_model=256, n_heads=2,
                      n_kv_heads=2, d_ff=0, vocab_size=64,
                      segments=(Segment((SLSTM,), 1),), dtype="float32")
    params = xlstm.init_slstm(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 12, cfg.d_model))
    y = jax.random.normal(jax.random.PRNGKey(2), (8, 12, cfg.d_model))

    def loss(p, x):
        out, _ = xlstm.apply_slstm(p, x, cfg=cfg)
        return jnp.mean((out - y) ** 2)

    grad = jax.jit(jax.value_and_grad(loss))
    ref_loss, ref_grads = grad(params, x)

    mesh = build_mesh((4, 2), ("data", "model"))
    xlstm._on_tpu = lambda: True
    rows = []
    scan = ops.slstm_scan
    ops.slstm_scan = lambda g, *a: rows.append(g.shape[0]) or scan(g, *a)
    specs = {"w_gates": P(None, "model"), "r_gates": P(),
             "w_out": P("model", None), "out_norm": {"scale": P()}}
    with jax.set_mesh(mesh):
        p = jax.device_put(params, shd.to_named(specs, mesh))
        xs = jax.device_put(x, jax.NamedSharding(mesh, P("data")))
        k_loss, k_grads = grad(p, xs)
    assert rows and set(rows) == {2}, rows
    np.testing.assert_allclose(float(k_loss), float(ref_loss), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(k_grads), jax.tree.leaves(ref_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    print("OK slstm_kernel_sharded")


def check_mlstm_gla_kernel_sharded():
    """The mLSTM block under a (2,2) mesh with the mixing on the GLA
    kernels (interpret mode) == ``chunked_gla`` on one device, loss and
    every parameter's gradient; each kernel call sees one data shard's
    rows."""
    from repro.configs.base import MLSTM, ModelConfig, Segment
    from repro.distributed import sharding as shd
    from repro.kernels.mlstm_gla import ops
    from repro.models import xlstm

    cfg = ModelConfig(name="mlstm-t", family="ssm", d_model=256, n_heads=4,
                      n_kv_heads=4, d_ff=0, vocab_size=64,
                      segments=(Segment((MLSTM,), 1),), dtype="float32")
    params = xlstm.init_mlstm(jax.random.PRNGKey(0), cfg)
    s = 2 * cfg.ssm.chunk
    x = jax.random.normal(jax.random.PRNGKey(1), (4, s, cfg.d_model))
    y = jax.random.normal(jax.random.PRNGKey(2), (4, s, cfg.d_model))

    def loss(p, x):
        out, _ = xlstm.apply_mlstm(p, x, cfg=cfg)
        return jnp.mean((out - y) ** 2)

    grad = jax.jit(jax.value_and_grad(loss))
    ref_loss, ref_grads = grad(params, x)

    mesh = build_mesh((2, 2), ("data", "model"))
    xlstm._on_tpu = lambda: True
    rows = []
    op = ops.mlstm_gla
    ops.mlstm_gla = lambda q, *a, **kw: rows.append(q.shape[0]) or op(
        q, *a, **kw)
    specs = {"w_up": P(None, "model"), "conv_w": P(None, "model"),
             "wq": P(None, "model"), "wk": P(None, "model"),
             "wv": P(None, "model"), "w_gates": P(),
             "out_norm": {"scale": P()}, "w_down": P("model", None)}
    with jax.set_mesh(mesh):
        p = jax.device_put(params, shd.to_named(specs, mesh))
        xs = jax.device_put(x, jax.NamedSharding(mesh, P("data")))
        k_loss, k_grads = grad(p, xs)
    assert rows and set(rows) == {2}, rows
    np.testing.assert_allclose(float(k_loss), float(ref_loss), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(k_grads), jax.tree.leaves(ref_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    print("OK mlstm_gla_kernel_sharded")


def check_compressed_psum():
    from repro.optim.grad_compress import compressed_psum_tree, ef_init

    mesh = build_mesh((8,), ("pod",))
    rng = np.random.RandomState(1)
    g = jnp.asarray(rng.randn(8, 64).astype(np.float32))

    def f(gi):
        grads = {"w": gi[0]}
        efs = {"w": ef_init(gi[0])}
        red, _ = compressed_psum_tree(grads, efs, jax.random.PRNGKey(0),
                                      axis_name="pod", method="int8")
        return red["w"][None]

    out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("pod"),),
                            out_specs=P("pod"), check_vma=False))(g)
    true = g.sum(0)
    got = np.asarray(out)[0]
    err = np.abs(got - np.asarray(true)).max() / (np.abs(np.asarray(true)).max())
    assert err < 0.05, err
    print("OK compressed_psum")


def check_serve_sharded_equals_single():
    """Sharded serve_step (prefill+decode) == single-device for zamba2."""
    from repro.configs import get_config
    from repro.configs.base import reduced
    from repro.distributed import sharding as shd
    from repro.models.lm import LM

    cfg = reduced(get_config("zamba2-1.2b"), repeats=1)
    lm = LM(cfg)
    key = jax.random.PRNGKey(0)
    params = lm.init(key)
    b, s = 4, 16
    rng = np.random.RandomState(0)
    prompt = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (b, s))),
              "positions": jnp.broadcast_to(jnp.arange(s)[None], (b, s))}
    caches = lm.caches(b, 32)
    lg1, c1 = jax.jit(lm.serve_step)(params, caches, prompt)

    mesh = build_mesh((4, 2), ("data", "model"))
    pspecs = shd.param_specs(cfg, jax.eval_shape(lambda: params), mesh)
    cspecs = shd.cache_specs(cfg, jax.eval_shape(lambda: caches), mesh)
    named = lambda t: shd.to_named(t, mesh)
    with jax.set_mesh(mesh):
        fn = jax.jit(lm.serve_step,
                     in_shardings=(named(pspecs), named(cspecs), None),
                     out_shardings=(None, named(cspecs)))
        c0 = jax.device_put(lm.caches(b, 32), named(cspecs))
        p0 = jax.device_put(params, named(pspecs))
        lg2, c2 = fn(p0, c0, prompt)
    np.testing.assert_allclose(np.asarray(lg1), np.asarray(jax.device_get(lg2)),
                               rtol=2e-3, atol=2e-3)
    print("OK serve_sharded")


if __name__ == "__main__":
    globals()[f"check_{sys.argv[1]}"]()
