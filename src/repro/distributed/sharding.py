"""Sharding rule engine: parameter-path → PartitionSpec.

Scheme (single pod: mesh ("data","model") = (16,16); multi-pod adds a
leading "pod" axis that joins the data-parallel group):

* TP (Megatron): projections col-sharded on their output feature dim /
  row-sharded on their input feature dim over "model"; embedding + LM head
  vocab-sharded.
* EP: MoE expert axis over "model" when divisible (deepseek 160/16);
  otherwise each expert's hidden dim is TP-sharded (granite 40e, d_exp 512).
  Very large routed-expert tensors (deepseek-v2) additionally FSDP-shard the
  expert hidden dim over "data".
* DP: batch dims over ("pod","data"). Sequence sharding replaces batch for
  long-context decode (batch < dp degree) — see batch/cache specs.
* ZeRO-1: optimizer master/moments additionally sharded over "data" on the
  largest divisible unsharded dim.

Every rule degrades to replication when a dim is not divisible by the axis
size (GSPMD uneven-sharding padding is avoided by construction so the
dry-run memory analysis stays honest).
"""
from __future__ import annotations

import re
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# FSDP-shard routed experts' hidden dim over "data" above this many params
FSDP_EXPERT_THRESHOLD = 30e9


def _axis_size(mesh: Mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, tuple):
        out = 1
        for n in name:
            out *= _axis_size(mesh, n)
        return out
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def _maybe(mesh, axis, dim: int):
    """axis if it divides dim, else None (replicate)."""
    return axis if axis and dim % _axis_size(mesh, axis) == 0 and dim >= _axis_size(mesh, axis) else None


def _path_str(path) -> str:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
    return "/".join(out)


def dp_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------
def _param_rule(cfg, pstr: str, shape, mesh) -> P:
    """Spec for ONE parameter (shape excludes any stacked leading dim)."""
    nd = len(shape)
    m = lambda ax, d: _maybe(mesh, ax, d)

    def col(i=-1):  # shard output feature dim
        spec = [None] * nd
        spec[i] = m("model", shape[i])
        return P(*spec)

    def row(i=0):   # shard input feature dim
        spec = [None] * nd
        spec[i] = m("model", shape[i])
        return P(*spec)

    leaf = pstr.rsplit("/", 1)[-1]

    if leaf == "embed":
        return P(m("model", shape[0]), None)           # vocab-sharded
    if leaf == "lm_head":
        return P(None, m("model", shape[1]))
    if "experts" in pstr:
        moe_params = cfg.moe.n_experts * 3 * cfg.d_model * cfg.moe.d_expert
        ep_ok = cfg.moe.n_experts % _axis_size(mesh, "model") == 0
        fsdp = moe_params * len([s for s in cfg.segments if not s.dense_ffn]) \
            > FSDP_EXPERT_THRESHOLD
        e_ax = "model" if ep_ok else None
        spec = [None] * nd
        spec[0] = m(e_ax, shape[0])
        if not ep_ok:
            # TP-mode experts (E % tp != 0, granite 40e): col-shard ALL
            # THREE matrices on their LAST dim — w_down sharded on its
            # output d, NOT on the contracted f. Sharding f makes the
            # w_down psum reduce the (E, C, d) capacity buffer (12.5× the
            # token count): 0.9 TB/step of all-reduce on granite (§Perf B3).
            # With d sharded, only (tokens, d) activations get re-gathered.
            spec[2] = m("model", shape[2])
        elif fsdp:
            f_dim = 2 if leaf in ("w_gate", "w_up") else 1
            spec[f_dim] = m("data", shape[f_dim])
        return P(*spec)
    if leaf == "router":
        return P(None, None)
    if leaf in ("wq", "wk", "wv", "w_gate", "w_up", "wq_a", "wq_b",
                "wkv_c", "wkv_b", "w_zx", "w_gates"):
        return col()
    if leaf in ("wo", "w_down", "w_out"):
        return row()
    if leaf in ("conv_x", "conv_w"):
        return col()
    if leaf in ("r_gates", "w_bcdt", "conv_bc", "wk_rope"):
        return P(*([None] * nd))
    # norms, scalars, biases
    return P(*([None] * nd))


def param_specs(cfg, shapes_tree, mesh: Mesh):
    """PartitionSpec pytree matching ``shapes_tree`` (from LM.init_shapes)."""

    def one(path, leaf):
        pstr = _path_str(path)
        shape = tuple(leaf.shape)
        stacked = "/stacked/" in "/" + pstr + "/"
        core = shape[1:] if stacked else shape
        spec = _param_rule(cfg, pstr, core, mesh)
        if stacked:
            spec = P(None, *spec)
        return spec

    return jax.tree_util.tree_map_with_path(one, shapes_tree)


# ---------------------------------------------------------------------------
# ZeRO-1: optimizer state gets an extra "data" shard
# ---------------------------------------------------------------------------
def zero_spec(spec: P, shape, mesh: Mesh) -> P:
    """Add 'data' sharding to the largest unsharded, divisible dim."""
    d = _axis_size(mesh, "data")
    entries = list(spec) + [None] * (len(shape) - len(spec))
    if "data" in jax.tree_util.tree_leaves(list(entries)):
        return spec
    best, best_size = None, 0
    for i, (ax, n) in enumerate(zip(entries, shape)):
        if ax is None and n % d == 0 and n >= d and n > best_size:
            best, best_size = i, n
    if best is None:
        return spec
    entries[best] = "data"
    return P(*entries)


def opt_specs(pspecs, shapes_tree, mesh: Mesh, zero1=True):
    """Optimizer-state specs: master/moments mirror params (+ZeRO-1)."""
    if not zero1:
        return pspecs

    def one(spec, shp):
        return zero_spec(spec, tuple(shp.shape), mesh)

    return jax.tree_util.tree_map(one, pspecs, shapes_tree)


# ---------------------------------------------------------------------------
# batch / cache / state specs
# ---------------------------------------------------------------------------
def batch_specs(cfg, batch_shapes, mesh: Mesh):
    dp = dp_axes(mesh)
    dpn = _axis_size(mesh, dp)

    def one(path, leaf):
        shape = tuple(leaf.shape)
        if len(shape) == 0:
            return P()
        if shape[0] % dpn == 0 and shape[0] >= dpn:
            return P(dp, *([None] * (len(shape) - 1)))
        # batch too small for DP (long-context decode): shard seq axis
        if len(shape) >= 2 and shape[1] % dpn == 0:
            return P(None, dp, *([None] * (len(shape) - 2)))
        return P(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(one, batch_shapes)


def cache_specs(cfg, cache_shapes, mesh: Mesh):
    """KV/state caches: batch over DP when divisible; otherwise (long_500k,
    batch=1) the cache *sequence* axis is sharded over DP (flash-decode
    style partial softmax — GSPMD inserts the psum)."""
    dp = dp_axes(mesh)
    dpn = _axis_size(mesh, dp)

    def one(path, leaf):
        pstr = _path_str(path)
        leafname = pstr.rsplit("/", 1)[-1]
        shape = tuple(leaf.shape)
        # caches are stacked over repeats: (repeats, batch, ...)
        spec = [None] * len(shape)
        if len(shape) <= 2:
            return P(*spec)
        seq_like = leafname in ("k", "v", "pos", "c_kv", "k_rope")
        if shape[1] % dpn == 0 and shape[1] >= dpn:
            spec[1] = dp                     # batch over DP
        elif seq_like and shape[2] % dpn == 0 and shape[2] >= dpn:
            spec[2] = dp                     # long-context: seq over DP
        if seq_like and len(shape) >= 5 and _maybe(mesh, "model", shape[3]):
            spec[3] = "model"                # kv heads over TP when divisible
        elif seq_like and spec[2] is None and shape[2] % _axis_size(mesh, "model") == 0:
            # heads not divisible (GQA kv=8 on TP=16): shard cache SEQ over
            # model instead — attention becomes a flash-decode partial
            # softmax with a psum over "model" (GSPMD inserts it).
            spec[2] = "model"
        return P(*spec)

    return jax.tree_util.tree_map_with_path(one, cache_shapes)


def state_specs(cfg, state_shapes, mesh: Mesh, zero1=True):
    """Specs for the full TrainState dict."""
    pspecs = param_specs(cfg, state_shapes["params"], mesh)
    ospecs = jax.tree_util.tree_map(
        lambda _: None, state_shapes["opt"])  # placeholder, replaced below
    ospecs = {
        k: opt_specs(pspecs, state_shapes["opt"][k], mesh, zero1)
        for k in state_shapes["opt"]
    }
    scalar = jax.tree_util.tree_map(lambda s: P(), state_shapes["ctrl"])
    return {
        "params": pspecs,
        "opt": ospecs,
        "ctrl": scalar,
        "step": P(),
        "rng": P(),
    }


def to_named(tree, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))


def under_mesh(fn, mesh: Optional[Mesh]):
    """``fn`` traced and dispatched with ``mesh`` in context
    (``jax.set_mesh``), so the model's ``collectives.constrain`` intents
    apply; ``fn`` itself when there is no mesh."""
    if mesh is None:
        return fn

    def call(*args, **kwargs):
        with jax.set_mesh(mesh):
            return fn(*args, **kwargs)
    return call


def compiler_options(mesh: Optional[Mesh]):
    """XLA options for a program partitioned over ``mesh``. On TPU, async
    collective fusion fuses the all-gather of model-sharded activations
    into the mLSTM chunk scan of an xLSTM step and then mis-schedules it:
    the compile fails with an ``hlo_schedule`` RET_CHECK (jaxlib 0.9.0).
    All-gathers therefore stay out of those fusions."""
    if mesh is None or jax.default_backend() != "tpu":
        return None
    return {"xla_tpu_enable_async_collective_fusion_fuse_all_gather": False}
