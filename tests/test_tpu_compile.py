"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e, with ``interpret=False`` and at real widths (xLSTM-350M's vocab,
seq 1024, pools of 12 and 768 rows; its sLSTM recurrence and mLSTM mixing
at seq 2048 over the 4 trained and 12 scored rows).

Interpret mode accepts block shapes and casts that the chip's compiler
refuses, so the CPU parity tests cannot see these failures. The TPU
compiler is installed with jaxlib; it compiles for a chip that is
described, not attached. Nothing runs, so these tests say nothing about
values — the ``*_ref`` parity tests cover those.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
every test file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ce_score.ce_score import (ce_score_block_pallas,
                                             ce_score_pallas)
from repro.kernels.fused_presample.fused_presample import (pool_keys_pallas,
                                                           row_score_pallas)
from repro.kernels.fused_presample.ops import _pruned_pool_score
from repro.kernels.mlstm_gla.mlstm_gla import (mlstm_gla_bwd_pallas,
                                               mlstm_gla_fwd_pallas)
from repro.kernels.slstm.slstm import (padded_rows, slstm_bwd_pallas,
                                      slstm_fwd_pallas)
from repro.kernels.topk_keys.topk_keys import race_keys_pallas

V, T, CHUNK = 50304, 1024, 128
D, NH, S = 1024, 4, 2048        # xlstm-350m's sLSTM: d_model, heads, seq
MLSTM_NH, MLSTM_HD, MLSTM_CHUNK = 4, 512, 256   # and its mLSTM's


@pytest.fixture(scope="module")
def v5e_2x2():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep it out of the cache
    jax.config.update("jax_enable_compilation_cache", False)
    return topo


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_ce_score_compiles(one_chip):
    _compile(functools.partial(ce_score_pallas, interpret=False), one_chip,
             ((12 * T, V), jnp.bfloat16), ((12 * T,), jnp.int32))


@pytest.mark.parametrize("B", [12, 768])
@pytest.mark.parametrize("block_b", [1, 8])
def test_ce_score_block_compiles(one_chip, B, block_b):
    _compile(functools.partial(ce_score_block_pallas, block_b=block_b,
                               interpret=False), one_chip,
             ((B, CHUNK, V), jnp.bfloat16), ((B, CHUNK), jnp.int32),
             ((B,), jnp.float32))


def test_pruned_pool_score_compiles(one_chip, monkeypatch):
    """The prod preset's scoring call: a 12-row pool, eight time chunks,
    the row-granular block the small-pool default picks. The op asks the
    default backend (the CPU here) whether to interpret; steer it to the
    chip's branch."""
    from repro.kernels.fused_presample import ops
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    fn = functools.partial(_pruned_pool_score, k=4, block_b=1, block_t=CHUNK,
                           block_v=2048, chunk_t=CHUNK, margin=1e-5)
    text = _compile(fn, one_chip, ((12, T, V), jnp.bfloat16),
                    ((12, T), jnp.int32), ((), jnp.uint32))
    assert text.count("tpu_custom_call") >= T // CHUNK


@pytest.mark.parametrize("B", [12, 768])
def test_row_score_compiles(one_chip, B):
    _compile(functools.partial(row_score_pallas, interpret=False), one_chip,
             ((B, T), jnp.float32), ((B, T), jnp.float32))


@pytest.mark.parametrize("n", [12, 100_000])
def test_pool_keys_compiles(one_chip, n):
    _compile(functools.partial(pool_keys_pallas, interpret=False), one_chip,
             ((n,), jnp.float32), ((1,), jnp.uint32), ((1,), jnp.float32))


def test_race_keys_compiles(one_chip):
    _compile(functools.partial(race_keys_pallas, interpret=False), one_chip,
             ((100_000,), jnp.float32), ((100_000,), jnp.float32),
             ((100_000,), jnp.uint32), ((1,), jnp.uint32),
             ((4,), jnp.float32))


def _slstm_state(b):
    return (((b, D), jnp.float32),) * 3


@pytest.mark.parametrize("b", [4, 12])
@pytest.mark.parametrize("save", [False, True])
def test_slstm_fwd_compiles(one_chip, b, save):
    _compile(functools.partial(slstm_fwd_pallas, save=save, interpret=False),
             one_chip, ((b, S, 4 * D), jnp.float32),
             ((NH, D // NH, 4 * D // NH), jnp.bfloat16), *_slstm_state(b))


@pytest.mark.parametrize("b", [4, 12])
def test_slstm_bwd_compiles(one_chip, b):
    bp = padded_rows(b)       # the forward's residuals are row-padded
    _compile(functools.partial(slstm_bwd_pallas, interpret=False), one_chip,
             ((b, S, D), jnp.float32), ((S, bp, 4 * D), jnp.float32),
             ((S, bp, D), jnp.float32), ((S, bp, D), jnp.float32),
             ((NH, D // NH, 4 * D // NH), jnp.bfloat16), *_slstm_state(b))


@pytest.mark.parametrize("b", [4, 12])
@pytest.mark.parametrize("save", [False, True])
def test_mlstm_gla_fwd_compiles(one_chip, b, save):
    """xlstm-350m's mLSTM mixing: 4 heads of 512 over seq 2048 in chunks
    of 256, the 4 trained rows (the update) and the 12 scored ones."""
    seq = ((b, S, MLSTM_NH * MLSTM_HD), jnp.bfloat16)
    row = ((b, MLSTM_NH, 1, S), jnp.float32)
    _compile(functools.partial(mlstm_gla_fwd_pallas, chunk=MLSTM_CHUNK,
                               save=save, mxu=jnp.bfloat16),
             one_chip, seq, seq, seq, row, row)


@pytest.mark.parametrize("b", [4, 12])
def test_mlstm_gla_bwd_compiles(one_chip, b):
    nc = S // MLSTM_CHUNK
    seq = ((b, S, MLSTM_NH * MLSTM_HD), jnp.bfloat16)
    row = ((b, MLSTM_NH, 1, S), jnp.float32)
    _compile(functools.partial(mlstm_gla_bwd_pallas, chunk=MLSTM_CHUNK,
                               mxu=jnp.bfloat16), one_chip,
             seq, seq, seq, row, row,
             ((b, MLSTM_NH, nc, MLSTM_HD, MLSTM_HD), jnp.bfloat16),
             ((b, MLSTM_NH, nc, 1, MLSTM_HD), jnp.float32), seq, row)


def _on_the_chip(mp):
    """The ops ask the default backend (the CPU here) which branch to
    take; steer them to the chip's."""
    from repro.kernels.ce_score import ops as ce_ops
    from repro.kernels.fused_presample import ops as fp_ops
    from repro.kernels.mlstm_gla import ops as gla_ops
    from repro.kernels.slstm import ops as slstm_ops
    from repro.kernels.topk_keys import ops as tk_ops
    from repro.models import xlstm
    for mod in (ce_ops, fp_ops, gla_ops, slstm_ops, tk_ops, xlstm):
        mp.setattr(mod, "_on_tpu", lambda: True)


def _kernel_calls(text, prefix):
    """(name, output shape) of each compiled Pallas call whose name starts
    with ``prefix``."""
    calls = [line.split(" = ", 1) for line in text.splitlines()
             if "tpu_custom_call" in line
             and line.split(" = ")[0].strip().lstrip("%").startswith(prefix)]
    return [(n.strip().lstrip("%").split(".")[0], out) for n, out in calls]


@pytest.fixture(scope="module")
def xlstm_programs(one_chip):
    """The update (``jit_host_step``: forward and backward) and the pool
    scoring (``jit_pruned``: forward) of xlstm-350m at the chip
    benchmark's shapes, lowered for the described chip."""
    from repro.api import Experiment
    from repro.api.config import build_run
    from repro.core.is_train import train_state_init
    b, ratio = 4, 3
    with pytest.MonkeyPatch.context() as mp:
        _on_the_chip(mp)
        exp = Experiment(build_run(arch="xlstm-350m", preset="prod",
                                   overrides={
            "shape.seq_len": S, "shape.global_batch": b,
            "imp.presample_ratio": ratio, "obs.sink": "none"}))

        def on_chip(tree):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one_chip), tree)
        state = on_chip(jax.eval_shape(
            lambda k: train_state_init(exp.lm, exp.opt, k),
            jax.random.PRNGKey(0)))
        rows = lambda n: {"tokens": jax.ShapeDtypeStruct((n, S), jnp.int32),
                          "labels": jax.ShapeDtypeStruct((n, S), jnp.int32)}
        batch = on_chip(dict(rows(b),
                             weights=jax.ShapeDtypeStruct((b,), jnp.float32)))
        args = (state, batch) + (
            (on_chip(jax.ShapeDtypeStruct((), jnp.float32)),)
            if exp.step_is_flagged else ())
        step = exp.step_fn.lower(*args).as_text()
        pool = on_chip(rows(b * ratio))
        pruned = exp.engine._fn_pruned(pool, b).lower(
            state["params"], pool,
            on_chip(jax.ShapeDtypeStruct((), jnp.uint32))).as_text()
    return step, pruned


def test_xlstm_programs_run_the_slstm_kernel(xlstm_programs):
    """Both programs run the sLSTM recurrence as the kernel."""
    step, pruned = xlstm_programs
    assert step.startswith("module @jit_host_step ")
    assert pruned.startswith("module @jit_pruned ")
    assert "slstm_fwd" in step and "slstm_bwd" in step
    assert "slstm_fwd" in pruned and "slstm_bwd" not in pruned


def test_xlstm_programs_run_the_mlstm_gla_kernels(xlstm_programs):
    """Both programs run the mLSTM mixing as the kernels: the update its
    forward (no residuals), the remat forward that saves the chunk
    states, and the backward; scoring the forward alone."""
    step, pruned = xlstm_programs
    for name in ("mlstm_gla_fwd\"", "mlstm_gla_fwd_res", "mlstm_gla_bwd"):
        assert name in step, name
    assert "mlstm_gla_fwd\"" in pruned
    assert "mlstm_gla_fwd_res" not in pruned
    assert "mlstm_gla_bwd" not in pruned


@pytest.fixture(scope="module")
def xlstm_sharded_step(v5e_2x2):
    """xlstm-350m's update on the four described chips as a (data=2,
    model=2) mesh, as ``chip_smoke.py --chips 4`` runs it, compiled."""
    from repro.api import Experiment
    from repro.api.config import build_run
    from repro.core.is_train import train_state_init
    from repro.distributed import sharding as shd
    from repro.launch.mesh import build_mesh
    b = 4
    with pytest.MonkeyPatch.context() as mp:
        _on_the_chip(mp)
        # the jitted step itself, and the chip's compiler options
        mp.setattr(shd, "under_mesh", lambda fn, mesh: fn)
        mp.setattr(shd, "compiler_options", lambda mesh: {
            "xla_tpu_enable_async_collective_fusion_fuse_all_gather": False})
        mesh = build_mesh((2, 2), ("data", "model"),
                          devices=v5e_2x2.devices)
        exp = Experiment(build_run(arch="xlstm-350m", preset="prod",
                                   overrides={
            "shape.seq_len": S, "shape.global_batch": b,
            "imp.presample_ratio": 3, "obs.sink": "none"}), mesh=mesh)
        state = jax.tree_util.tree_map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=sh),
            jax.eval_shape(lambda k: train_state_init(exp.lm, exp.opt, k),
                           jax.random.PRNGKey(0)), exp.state_shardings)
        dp = jax.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))
        batch = {k: jax.ShapeDtypeStruct((b, S), jnp.int32, sharding=dp)
                 for k in ("tokens", "labels")}
        batch["weights"] = jax.ShapeDtypeStruct((b,), jnp.float32,
                                                sharding=dp)
        args = (state, batch) + ((jax.ShapeDtypeStruct(
            (), jnp.float32, sharding=jax.NamedSharding(
                mesh, jax.sharding.PartitionSpec())),)
            if exp.step_is_flagged else ())
        with jax.set_mesh(mesh):
            return b, exp.step_fn.lower(*args).compile().as_text()


def test_xlstm_sharded_step_runs_the_slstm_kernel_per_shard(
        xlstm_sharded_step):
    """The sLSTM kernels, each on its data shard's 2 of the 4 rows. A
    Mosaic call cannot be partitioned by XLA; unwrapped it fails to
    compile."""
    b, text = xlstm_sharded_step
    calls = _kernel_calls(text, "slstm")
    assert {n for n, _ in calls} == {"slstm_fwd", "slstm_bwd"}
    # each call's hs (fwd) or dg (bwd) holds one shard's rows
    assert all(out.startswith(f"(f32[{b // 2},{S},") for _, out in calls)


def test_xlstm_sharded_step_runs_the_mlstm_gla_kernels_per_shard(
        xlstm_sharded_step):
    """The mLSTM kernels, each on its data shard's 2 of the 4 rows, every
    head whole."""
    b, text = xlstm_sharded_step
    calls = _kernel_calls(text, "mlstm_gla")
    assert {n for n, _ in calls} == {"mlstm_gla_fwd", "mlstm_gla_fwd_res",
                                     "mlstm_gla_bwd"}
    # each call's first output, y (fwd) or dq (bwd), holds one shard's rows
    width = MLSTM_NH * MLSTM_HD
    assert all(out.startswith(f"(bf16[{b // 2},{S},{width}]")
               for _, out in calls), calls
