"""Faults planted in the program's timed path, each where its result is
produced: a run with one of them must come out not ``correct``.

Each is ``fault(exp, seed)``, applied to a built experiment before
``fit``. ``FAULTS`` lists those a cell of each scheme can have. They are
read on the chip by ``calibrate.py faults`` and driven through a small
run on the CPU by ``tests/test_chip_harness.py``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare


def _raw_step(exp):
    from repro.core.is_train import StepSpec, build_step
    return build_step(exp.lm, exp.run, exp.opt, StepSpec("host"))


def _replace_step(exp, step):
    exp.step_fn = jax.jit(step, donate_argnums=0)


def state_unchanged(exp, seed):
    """The step returns its state as it came."""
    raw = _raw_step(exp)
    _replace_step(exp, lambda s, b, f: (s, raw(s, b, f)[1]))


def half_batch(exp, seed):
    """Half of each batch left out, the mean taken over the rest: inside
    the step, the weights of the second half of the rows are 0 and those
    of the first half doubled. The forward still covers every row, so the
    step's score vector keeps its length."""
    raw = _raw_step(exp)

    def step(s, b, f):
        n = b["labels"].shape[0]
        w = b.get("weights")
        w = jnp.ones((n,), jnp.float32) if w is None else w
        w = jnp.where(jnp.arange(n) < n // 2, 2 * w, 0).astype(w.dtype)
        return raw(s, dict(b, weights=w), f)
    _replace_step(exp, step)


def score_altered(exp, seed):
    """One pool row's Ĝ, of a row the pass scores in full, × 1.5 where the
    scoring program produces it."""
    orig = exp.engine.score_select

    def score_select(params, batch, prune=None):
        out = orig(params, batch, prune=prune)
        fut = list(out["fut"])
        row = jnp.argmax(fut[2])                 # the first survivor
        fut[1] = fut[1].at[row].multiply(1.5)
        return dict(out, fut=tuple(fut))
    exp.engine.score_select = score_select


def row_score_altered(exp, seed):
    """One trained row's Ĝ × 1.5 where the train step produces it."""
    raw = _raw_step(exp)

    def step(s, b, f):
        s, m = raw(s, b, f)
        m = dict(m, sample_scores=m["sample_scores"].at[0].multiply(1.5))
        return s, m
    _replace_step(exp, step)


def _alter_plan(exp, change):
    orig = exp.sampler._select_plan

    def select_plan(cplan, scores, step):
        return change(orig(cplan, scores, step), scores, step)
    exp.sampler._select_plan = select_plan


def selection_altered(exp, seed):
    """The kept row with the smallest race key swapped for the row left
    out with the largest, where the host selection produces the plan."""
    def change(plan, scores, step):
        r = compare.keys(scores, seed, step)
        kept = np.asarray(plan.src_rows).copy()
        out = np.setdiff1d(np.arange(len(r)), kept)
        kept[np.argmin(r[kept])] = out[np.argmax(r[out])]
        return dataclasses.replace(plan, src_rows=kept)
    _alter_plan(exp, change)


def weights_altered(exp, seed):
    """Unit weights in place of the Horvitz–Thompson ones."""
    def change(plan, scores, step):
        return dataclasses.replace(plan, weights=np.ones_like(plan.weights))
    _alter_plan(exp, change)


FAULTS = {"presample": [state_unchanged, half_batch, score_altered,
                        row_score_altered, selection_altered,
                        weights_altered],
          "uniform": [state_unchanged, half_batch, row_score_altered]}
BY_NAME = {f.__name__: f for fs in FAULTS.values() for f in fs}
