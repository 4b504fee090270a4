"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e, with ``interpret=False`` and at real widths (xLSTM-350M's vocab,
seq 1024, pools of 12 and 768 rows).

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
from repro.kernels.topk_keys.topk_keys import race_keys_pallas

V, T, CHUNK = 50304, 1024, 128


@pytest.fixture(scope="module")
def one_chip():
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
    return SingleDeviceSharding(topo.devices[0])


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
