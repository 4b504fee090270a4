"""The plain float32 reference of a configuration, and its weights.

The configuration file (``configs/<name>.json``) gives the sizes and the
layer pattern; each block kind's equations live in ``refs/<kind>.py``,
found by name, so a configuration with a new kind adds a file and edits
none. The decoder is token embedding → segments (each a pattern of block
kinds repeated) → RMSNorm → logits against the embedding (tied) or an
output head.

``make_params`` draws the weights from the seed, in the tree layout and
dtypes the program trains (the harness checks the layout against the
program's own), so the program and the reference start from one set of
weights that neither side's code made for the other. Everything else
here is float32 and imports nothing of the program.

On a cell of several chips a ``Placement`` lays the reference's arrays
over them; the mathematics stay as they are, and without one (a one-chip
cell) every call runs as it always has.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chipbench.numerics import Numerics
from chipbench.refs.common import F32, ONES, dense, rmsnorm

ROW_BLOCK = 4      # rows per reference call: bounds the (rows, T, T) forms


def block_module(kind):
    return importlib.import_module(f"chipbench.refs.{kind}")


def seed_key(seed):
    """A PRNG key from any whole seed, wider ones included."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


# ---------------------------------------------------------------------------
# placement over a cell's chips
# ---------------------------------------------------------------------------
class Placement:
    """Where the reference's arrays lie on a cell of several chips, a
    (data, model) grid of ``devices``: every leaf of the weights, the
    optimizer's moments and the gradient split over all the chips along
    its largest axis that their number divides (replicated where none
    does); the rows of a call over the data axis."""

    def __init__(self, devices, data, model):
        self.mesh = Mesh(np.asarray(devices).reshape(data, model),
                         ("data", "model"))

    # a static argument of the reference's jitted calls: equal placements
    # share their compiled programs
    def __eq__(self, other):
        return isinstance(other, Placement) and self.mesh == other.mesh

    def __hash__(self):
        return hash(self.mesh)

    def leaf(self, shape):
        n = self.mesh.size
        axes = [i for i, s in enumerate(shape) if s % n == 0]
        if not axes:
            return NamedSharding(self.mesh, P())
        i = max(axes, key=lambda a: shape[a])
        return NamedSharding(self.mesh, P(*[("data", "model") if a == i
                                            else None for a in range(i + 1)]))

    def tree(self, tree):
        """The shardings of a tree of arrays (or of their shapes)."""
        return jax.tree_util.tree_map(lambda x: self.leaf(x.shape), tree)

    def constrain(self, tree):
        return jax.tree_util.tree_map(
            lambda x: jax.lax.with_sharding_constraint(x, self.leaf(x.shape)),
            tree)

    def rows(self, x):
        """A block of rows on the chips, split over the data axis where
        the data axis divides them."""
        x = jnp.asarray(x)
        split = x.shape[0] % self.mesh.shape["data"] == 0
        return jax.device_put(x, NamedSharding(
            self.mesh, P("data") if split else P()))


def _rows(x, place):
    return jnp.asarray(x) if place is None else place.rows(x)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def param_spec(cfg):
    """Nested dict of (shape, dtype, init) leaves, in the program's layout:
    per-repeat parameters stacked on a leading axis per pattern position,
    a shared block's parameters once per segment."""
    d, V = cfg["d_model"], cfg["vocab_size"]
    out = {"embed": ((V, d), "model", ("normal", 0.02)), "segments": {}}
    for i, seg in enumerate(cfg["segments"]):
        stacked, s = {}, {}
        for pi, kind in enumerate(seg["pattern"]):
            mod = block_module(kind)
            if getattr(mod, "SHARED", False):
                stacked[f"p{pi}"] = {}
                s["shared"] = mod.spec(cfg)
            else:
                stacked[f"p{pi}"] = jax.tree_util.tree_map(
                    lambda l, r=seg["repeats"]: ((r,) + l[0], l[1], l[2]),
                    mod.spec(cfg), is_leaf=_is_spec)
        s["stacked"] = stacked
        out["segments"][f"seg{i}"] = s
    out["final_norm"] = {"scale": ((d,), "model", ONES)}
    if not cfg["tie_embeddings"]:
        out["lm_head"] = ((d, V), "model", dense(d))
    return out


def _is_spec(x):
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def _leaf(key, spec, model_dtype):
    shape, dt, init = spec
    dtype = jnp.dtype(model_dtype if dt == "model" else dt)
    if init[0] == "normal":
        v = init[1] * jax.random.truncated_normal(key, -2.0, 2.0, shape, F32)
    elif init[0] == "ones":
        v = jnp.ones(shape, F32)
    elif init[0] == "zeros":
        v = jnp.zeros(shape, F32)
    elif init[0] == "log_linspace":
        v = jnp.log(jnp.linspace(init[1], init[2], shape[-1], dtype=F32))
        v = jnp.broadcast_to(v, shape)
    else:
        raise ValueError(f"unknown init {init!r}")
    return v.astype(dtype)


def _build(key, *, cfg_key):
    cfg = _CFGS[cfg_key]
    leaves, tree = jax.tree_util.tree_flatten(param_spec(cfg),
                                              is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(
        tree, [_leaf(k, s, cfg["dtype"]) for k, s in zip(keys, leaves)])


_build_jit = jax.jit(_build, static_argnames=("cfg_key",))


def make_params(cfg, seed, shardings=None):
    """The weights of one run, on the device, from the seed: one jitted
    call, in the dtypes the program trains them in; with ``shardings``
    (a tree of them), each leaf made where it lies."""
    build = _build_jit if shardings is None else jax.jit(
        _build, static_argnames=("cfg_key",), out_shardings=shardings)
    return build(seed_key(seed), cfg_key=_register(cfg))


def param_shapes(cfg):
    return jax.eval_shape(lambda: make_params(cfg, 0))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def hidden(params, tokens, cfg, num):
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    for i, seg in enumerate(cfg["segments"]):
        sp = params["segments"][f"seg{i}"]
        mods = [block_module(k) for k in seg["pattern"]]

        def body(x, stacked, sp=sp, mods=mods):
            for pi, mod in enumerate(mods):
                p = sp["shared"] if getattr(mod, "SHARED", False) \
                    else stacked[f"p{pi}"]
                x = mod.apply(p, x, cfg, num)
            return x, None

        x, _ = jax.lax.scan(jax.checkpoint(body), x, sp["stacked"])
    return rmsnorm(x, params["final_norm"]["scale"], cfg["norm_eps"])


def _head_w(params, cfg):
    return params["embed"].T if cfg["tie_embeddings"] else params["lm_head"]


def row_stats(h, labels, w_head, num):
    """One row's (mean CE, Ĝ² = Σ_t ‖softmax(z_t) − onehot(y_t)‖²),
    from its final hidden states (T, d)."""
    z = num.mm(h, w_head)                                    # (T, V)
    lse = jax.nn.logsumexp(z, axis=-1)
    zy = jnp.take_along_axis(z, labels[:, None], axis=-1)[:, 0]
    p = jnp.exp(z - lse[:, None])
    py = jnp.exp(zy - lse)
    g2 = jnp.sum(p * p, axis=-1) - 2.0 * py + 1.0
    return jnp.mean(lse - zy), jnp.sum(g2)


@functools.partial(jax.jit, static_argnames=("cfg_key", "mode"))
def _pool_stats(params, tokens, labels, *, cfg_key, mode):
    cfg, num = _CFGS[cfg_key], Numerics(mode)
    params = jax.tree_util.tree_map(lambda a: a.astype(F32), params)
    h = hidden(params, tokens, cfg, num)
    w = _head_w(params, cfg)
    ce, g2 = jax.lax.map(lambda hl: row_stats(hl[0], hl[1], w, num),
                         (h, labels))
    return ce, jnp.sqrt(jnp.maximum(g2, 1e-20))


def _loss(params, tokens, labels, weights, cfg, num):
    """Σ_rows w_i·CE_i / (rows in the step): one block of the step's
    weighted mean loss; and each row's Ĝ from the same forward."""
    h = hidden(params, tokens, cfg, num)
    w = _head_w(params, cfg)
    ce, g2 = jax.lax.map(
        jax.checkpoint(lambda hl: row_stats(hl[0], hl[1], w, num)),
        (h, labels))
    return jnp.sum(ce * weights), jnp.sqrt(jnp.maximum(g2, 1e-20))


@functools.partial(jax.jit, static_argnames=("cfg_key", "mode", "place"))
def _loss_grad(params, tokens, labels, weights, *, cfg_key, mode,
               place=None):
    out, grad = jax.value_and_grad(_loss, has_aux=True)(
        params, tokens, labels, weights, _CFGS[cfg_key], Numerics(mode))
    return out, grad if place is None else place.constrain(grad)


_CFGS = {}


def _register(cfg):
    key = repr(sorted((k, repr(v)) for k, v in cfg.items()))
    _CFGS[key] = cfg
    return key


def pool_stats(params, tokens, labels, cfg, mode="f32", place=None):
    """Per-row (mean CE, Ĝ) of a pool, ROW_BLOCK rows per call; numpy."""
    key = _register(cfg)
    ce, sc = [], []
    for i in range(0, tokens.shape[0], ROW_BLOCK):
        c, s = _pool_stats(params, _rows(tokens[i:i + ROW_BLOCK], place),
                           _rows(labels[i:i + ROW_BLOCK], place),
                           cfg_key=key, mode=mode)
        ce.append(np.asarray(c, np.float64))
        sc.append(np.asarray(s, np.float64))
    return np.concatenate(ce), np.concatenate(sc)


def loss_and_grad(params, tokens, labels, weights, cfg, mode="f32",
                  row_scores=False, place=None):
    """The step's loss mean_i(w_i·CE_i) and its gradient (f32 tree); with
    ``row_scores``, also each row's Ĝ (numpy)."""
    key = _register(cfg)
    n = tokens.shape[0]
    loss, grad, scores = 0.0, None, []
    for i in range(0, n, ROW_BLOCK):
        w = _rows(np.asarray(weights[i:i + ROW_BLOCK], np.float32) / n, place)
        (l, sc), g = _loss_grad(params, _rows(tokens[i:i + ROW_BLOCK], place),
                                _rows(labels[i:i + ROW_BLOCK], place), w,
                                cfg_key=key, mode=mode, place=place)
        loss += float(l)
        scores.append(np.asarray(sc, np.float64))
        grad = g if grad is None else jax.tree_util.tree_map(jnp.add, grad, g)
    if row_scores:
        return loss, grad, np.concatenate(scores)
    return loss, grad


# ---------------------------------------------------------------------------
# AdamW, as the job states it
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps", "wd",
                                             "clip", "place"),
                   donate_argnums=(0, 1, 2))
def adamw_step(params, m, v, grads, step, *, lr, b1, b2, eps, wd, clip,
               place=None):
    """Global-norm clip, then decoupled-weight-decay Adam with bias
    correction. Returns (params, m, v, the clipped gradient, the global
    gradient norm before the clip); with ``place``, the trees laid out by
    it."""
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-12)) if clip > 0 \
        else 1.0
    g = jax.tree_util.tree_map(lambda x: x * scale, grads)
    t = step + 1.0
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    m = jax.tree_util.tree_map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree_util.tree_map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
    params = jax.tree_util.tree_map(
        lambda p, a, s: p - lr * ((a / c1) / (jnp.sqrt(s / c2) + eps)
                                  + wd * p), params, m, v)
    if place is not None:
        params, m, v, g = place.constrain((params, m, v, g))
    return params, m, v, g, gn


@jax.jit
def _norms(xs):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(F32)))) for x in xs]


@jax.jit
def _diff_norms(xs, ys):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(F32) - y.astype(F32))))
            for x, y in zip(xs, ys)]


def _fmix(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


@functools.partial(jax.jit, static_argnames=("k",))
def _sketch(xs, k):
    out = jnp.zeros((k,), F32)
    for li, x in enumerate(xs):
        flat = x.reshape(-1).astype(F32)
        idx = jnp.arange(flat.size, dtype=jnp.uint32)

        def proj(j, flat=flat, idx=idx, li=li):
            h = _fmix(idx * jnp.uint32(0x9E3779B9)
                      ^ (j * jnp.uint32(0x85EBCA6B) + jnp.uint32(li)))
            return jnp.sum(flat * (1.0 - 2.0 * (h >> 31).astype(F32)))
        out = out + jax.lax.map(proj, jnp.arange(k, dtype=jnp.uint32))
    return out


@jax.jit
def _row_sq(x):
    return jnp.sum(jnp.square(x.astype(F32)), axis=1)


def row_sq(x):
    """Squared norm of each row of a matrix (numpy, float64)."""
    return np.asarray(_row_sq(x), np.float64)


def sketch(tree, k=64):
    """k random ±1 projections of the whole tree (fixed signs per element):
    ‖sketch(a) − sketch(b)‖ / ‖sketch(b)‖ estimates ‖a − b‖ / ‖b‖."""
    return [float(x) for x in _sketch(jax.tree_util.tree_leaves(tree), k)]


def leaf_norms(tree):
    """{path: L2 norm} of every leaf, as Python floats."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = _norms([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in
            zip(flat, norms)}


def diff_norms(a, b):
    """{path: ‖a − b‖} per leaf (f32 difference)."""
    fa = jax.tree_util.tree_flatten_with_path(a)[0]
    norms = _diff_norms([x for _, x in fa], jax.tree_util.tree_leaves(b))
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in
            zip(fa, norms)}


def flops_per_row(cfg, T):
    """Model FLOPs of one row's forward pass: every layer, and the
    logits (the embedding lookup is free)."""
    total = 2.0 * T * cfg["d_model"] * cfg["vocab_size"]
    for seg in cfg["segments"]:
        for kind in seg["pattern"]:
            total += seg["repeats"] * block_module(kind).forward_flops(cfg, T)
    return total
