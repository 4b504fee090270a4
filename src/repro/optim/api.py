"""Optimizers (built from scratch — no optax in this environment).

Contract:
    opt = get_optimizer(OptimConfig, schedule_fn)
    state = opt.init(params)
    new_params, new_state, metrics = opt.update(grads, state, params, step)

Mixed precision: parameters may be bf16; the optimizer keeps an f32 master
copy + f32 moments in its state and casts back to the parameter dtype after
the update. With ``zero1`` the state is additionally sharded over the data
axis (see distributed/sharding.zero_spec).
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp


class Optimizer(NamedTuple):
    init: Callable
    update: Callable


def _master(params):
    """The f32 master copy — a COPY even where params are already f32, so
    no parameter shares a buffer with its master (a donated train state
    must not donate one buffer twice)."""
    return jax.tree_util.tree_map(
        lambda p: jnp.array(p, jnp.float32, copy=True), params)


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree_util.tree_leaves(tree)))


def _clip_by_global_norm(grads, max_norm):
    gn = _global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-12))
    return jax.tree_util.tree_map(lambda g: g * scale, grads), gn


def constant_schedule(lr):
    return lambda step: jnp.asarray(lr, jnp.float32)


def step_drop_schedule(lr, drops, factor=0.2):
    """The paper's CIFAR schedule: lr divided at fixed update counts."""
    def f(step):
        mult = jnp.ones((), jnp.float32)
        for d in drops:
            mult = jnp.where(step >= d, mult * factor, mult)
        return lr * mult
    return f


def warmup_cosine_schedule(lr, warmup, total):
    def f(step):
        step = step.astype(jnp.float32)
        warm = step / jnp.maximum(warmup, 1)
        prog = jnp.clip((step - warmup) / jnp.maximum(total - warmup, 1), 0.0, 1.0)
        cos = 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
        return lr * jnp.where(step < warmup, warm, cos)
    return f


def sgd(cfg, schedule=None):
    """SGD with momentum (+ optional Nesterov), decoupled weight decay."""
    sched = schedule or constant_schedule(cfg.lr)

    def init(params):
        return {
            "master": _master(params),
            "mu": jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
        }

    def update(grads, state, params, step):
        lr = sched(step)
        if cfg.grad_clip > 0:
            grads, gn = _clip_by_global_norm(grads, cfg.grad_clip)
        else:
            gn = _global_norm(grads)

        def upd(g, m, mu):
            g = g.astype(jnp.float32)
            if cfg.weight_decay:
                g = g + cfg.weight_decay * m
            mu = cfg.momentum * mu + g
            d = (g + cfg.momentum * mu) if cfg.nesterov else mu
            return m - lr * d, mu

        flat = jax.tree_util.tree_map(upd, grads, state["master"], state["mu"])
        master = jax.tree_util.tree_map(lambda x: x[0], flat,
                                        is_leaf=lambda x: isinstance(x, tuple))
        mu = jax.tree_util.tree_map(lambda x: x[1], flat,
                                    is_leaf=lambda x: isinstance(x, tuple))
        new_params = jax.tree_util.tree_map(
            lambda m, p: m.astype(p.dtype), master, params)
        return new_params, {"master": master, "mu": mu}, {"grad_norm": gn, "lr": lr}

    return Optimizer(init, update)


def adamw(cfg, schedule=None):
    sched = schedule or constant_schedule(cfg.lr)

    def init(params):
        z = lambda p: jnp.zeros(p.shape, jnp.float32)
        return {
            "master": _master(params),
            "m": jax.tree_util.tree_map(z, params),
            "v": jax.tree_util.tree_map(z, params),
        }

    def update(grads, state, params, step):
        lr = sched(step)
        if cfg.grad_clip > 0:
            grads, gn = _clip_by_global_norm(grads, cfg.grad_clip)
        else:
            gn = _global_norm(grads)
        t = step.astype(jnp.float32) + 1.0
        c1 = 1.0 - cfg.b1 ** t
        c2 = 1.0 - cfg.b2 ** t

        def upd(g, ms, m, v):
            g = g.astype(jnp.float32)
            m = cfg.b1 * m + (1 - cfg.b1) * g
            v = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g)
            mh = m / c1
            vh = v / c2
            ms = ms - lr * (mh / (jnp.sqrt(vh) + cfg.eps) + cfg.weight_decay * ms)
            return ms, m, v

        flat = jax.tree_util.tree_map(upd, grads, state["master"],
                                      state["m"], state["v"])
        pick = lambda i: jax.tree_util.tree_map(
            lambda x: x[i], flat, is_leaf=lambda x: isinstance(x, tuple))
        master, m, v = pick(0), pick(1), pick(2)
        new_params = jax.tree_util.tree_map(
            lambda ms, p: ms.astype(p.dtype), master, params)
        return new_params, {"master": master, "m": m, "v": v}, \
            {"grad_norm": gn, "lr": lr}

    return Optimizer(init, update)


def get_optimizer(cfg, schedule=None) -> Optimizer:
    if cfg.name == "sgd":
        return sgd(cfg, schedule)
    if cfg.name == "adamw":
        return adamw(cfg, schedule)
    raise ValueError(cfg.name)
