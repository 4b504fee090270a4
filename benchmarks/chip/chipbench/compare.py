"""The comparison that decides ``correct``.

Every number is a gap between the program and the float32 reference over
the checked steps (``harness.CHECK_STEPS``); a cell compares those its
``limits/<workload>.json`` names, each against its limit (how each limit
was set, and why the others are not compared: PERF.md).

``loss_gap``       each step's training loss, |L_p − L_r| / |L_r|, worst step
``grad_gap``       the first gradient as the optimizer gets it (clipped),
                   per leaf: |‖g_p‖ − ‖g_r‖| / max(‖g_r‖, median leaf ‖g_r‖),
                   worst leaf; the program's is read from AdamW's first
                   moment after one step, m = (1 − β1)·g
``grad_dir_gap``   the same gradient element by element, ‖g_p − g_r‖/‖g_r‖,
                   estimated from 64 fixed random ±1 projections of each
``row_grad_gap``   each trained row's share of the first gradient: per
                   row of the first step, the norm of the (unclipped)
                   gradient's embedding rows of the tokens that this row
                   alone of the step's rows holds, which only this row's
                   weighted loss reaches; |‖·‖_p − ‖·‖_r| / ‖·‖_r, worst
                   row. A row left out, or weighted wrong, reads its gap
``update_gap``     the weights' change over the checked steps, per leaf as
                   ``grad_gap``, worst leaf; leaves whose reference gradient
                   is under a thousandth of the median leaf's (moved by
                   Adam's round-off alone) are left out
``row_score_gap``  each step's per-row Ĝ of the rows it trained on, as the
                   step itself reports them, against the reference's from
                   its own forward of the same step; a row missing on
                   either side reads 1
``score_gap``      every pool row's Ĝ that the pruned pass scored in full
                   (its survivors), |s_p − s_r| / s_r, worst row
``pool_loss_gap``  the survivors' mean CE in the pools scored with the
                   initial weights, which both sides share: the root mean
                   square over those rows of the relative gap (a reading,
                   not compared: PERF.md)
``select_gap``     the selection: the reference's race keys of the rows the
                   program kept against those it did not; how far the worst
                   kept key lies above the best key left out, relative (0
                   when the program kept exactly the reference's top b)
``weight_gap``     the unbiasing weights of the kept rows, relative, worst
``gate_shut_steps`` measured steps on which the τ gate was shut (the IS
                   cells hold it open)

The selection race is the program's documented one (survival-closed
presample race, ``src/repro/sampler/selection.py``): race key
r_i = −log(u_i) / s_i with u_i a counter hash of (seed, step, pool row),
the b smallest keys kept, Horvitz–Thompson weights 1/(B·(1 − exp(−s_i·r*)))
off the (b+1)-th key r*. The hash below is a copy of its arithmetic.
"""
from __future__ import annotations

import numpy as np

SALT = 4211                # the presample scheme's hash salt
_M32 = 0xFFFFFFFF


def _fmix32(x):
    x = np.asarray(x, np.uint64) & _M32
    x ^= x >> np.uint64(16)
    x = (x * np.uint64(0x85EBCA6B)) & _M32
    x ^= x >> np.uint64(13)
    x = (x * np.uint64(0xC2B2AE35)) & _M32
    x ^= x >> np.uint64(16)
    return x


def hash_context(seed, step):
    c = (int(seed) ^ (SALT * 0x9E3779B9) ^ (int(step) * 0xC2B2AE3D)) & _M32
    return int(_fmix32(np.uint64(c)))


def uniforms(n, ctx):
    """u(row, ctx) ∈ (0, 1) for pool rows 0..n−1."""
    g = np.arange(n, dtype=np.uint64)
    x = g & _M32                                    # high word is 0
    h = _fmix32(((x * np.uint64(0x9E3779B9)) & _M32) ^ np.uint64(ctx))
    h = _fmix32((h + np.uint64(0x6A09E667)) & _M32)
    return (h >> np.uint64(8)).astype(np.float64) * 2.0 ** -24 + 2.0 ** -25


def keys(scores, seed, step):
    s = np.asarray(scores, np.float64)
    return -np.log(uniforms(s.size, hash_context(seed, step))) \
        / np.maximum(s, 1e-20)


def race(scores, k, seed, step):
    r = keys(scores, seed, step)
    order = np.lexsort((np.arange(r.size), r))
    return {"idx": order[:k].astype(np.int64), "keys": r}


def race_weights(scores, kept, seed, step):
    """HT weights of the kept rows, off the smallest key left out."""
    s = np.asarray(scores, np.float64)
    r = keys(s, seed, step)
    out = np.ones(s.size, bool)
    out[np.asarray(kept)] = False
    thr = float(np.min(r[out]))
    pi = np.maximum(-np.expm1(-np.maximum(s[kept], 1e-20) * thr), 1e-300)
    return 1.0 / (s.size * pi)


def select_gap(scores, kept, seed, step):
    r = keys(scores, seed, step)
    out = np.ones(r.size, bool)
    out[np.asarray(kept)] = False
    worst_in, best_out = float(np.max(r[~out])), float(np.min(r[out]))
    return max(0.0, (worst_in - best_out) / best_out)


def leaf_gap(prog, ref, keep=None):
    """Worst leaf's |‖p‖ − ‖r‖| / max(‖r‖, median ‖r‖), and its name."""
    names = [k for k in ref if keep is None or k in keep]
    med = float(np.median([ref[k] for k in ref]))
    worst, at = 0.0, None
    for k in names:
        g = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if g >= worst:
            worst, at = g, k
    return worst, at


def moved_leaves(grad_norms):
    med = float(np.median(list(grad_norms.values())))
    return {k for k, v in grad_norms.items() if v >= 1e-3 * med}


def row_gap(prog_rows, ref_rows):
    """Worst relative gap of per-row values over the steps; a row that one
    side lacks reads 1."""
    worst = 0.0
    for p, r in zip(prog_rows, ref_rows):
        if p is None or r is None or len(p) != len(r):
            return 1.0
        p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
        worst = max(worst, float(np.max(np.abs(p - r) / np.abs(r))))
    return worst


def owner_masks(rows, vocab):
    """Per token id, the bit set of the rows (of one step) that hold it."""
    owner = np.zeros((vocab,), np.int64)
    for i, r in enumerate(rows):
        owner[np.unique(np.asarray(r))] |= 1 << i
    return owner


def row_grads(embed_sq, owner, n):
    """Per row: the norm over the embedding rows of the tokens that row
    alone holds (None where it holds none)."""
    sq = np.asarray(embed_sq, np.float64)
    out = []
    for i in range(n):
        alone = owner == (1 << i)
        out.append(float(np.sqrt(np.sum(sq[alone]))) if alone.any()
                   else None)
    return out


def row_grad_gap(prog, ref):
    n = len(ref["row_scores"][0])
    p = row_grads(prog["embed_sq"], ref["owner"], n)
    r = row_grads(ref["embed_sq"], ref["owner"], n)
    return max(abs(a - c) / c for a, c in zip(p, r) if c)


def initial_pools(ref):
    """Checked steps whose pool was scored with the initial weights: the
    loop scores pool k with the weights before update k − 1."""
    return [s for s in ref["pools"] if max(int(s) - 1, 0) == 0]


def gaps(prog, ref, tr, seed):
    """Every number, by name (no limits)."""
    out = {}
    out["loss_gap"] = max(abs(p - r) / abs(r) for p, r in
                          zip(prog["losses"], ref["losses"]))
    sp = np.asarray(prog["grad_sketch"], np.float64)
    sr = np.asarray(ref["grad_sketch"], np.float64)
    out["grad_dir_gap"] = float(np.linalg.norm(sp - sr) / np.linalg.norm(sr))
    out["row_score_gap"] = row_gap(prog["row_scores"], ref["row_scores"])
    out["row_grad_gap"] = row_grad_gap(prog, ref)
    out["grad_gap"], out["grad_gap_leaf"] = leaf_gap(prog["grad_norms"],
                                                     ref["grad_norms"])
    out["update_gap"], out["update_gap_leaf"] = leaf_gap(
        prog["update_norms"], ref["update_norms"],
        keep=moved_leaves(ref["grad_norms"]))
    if tr["scheme"] == "presample":
        sg = sel = wg = 0.0
        sq = []
        first = initial_pools(ref)
        for step, rp in ref["pools"].items():
            pp = prog["pools"][step]
            if not np.array_equal(pp["gids"], rp["gids"]):
                raise RuntimeError(f"step {step}: the program's pool is "
                                   f"not the traffic's")
            a = np.asarray(pp["alive"], bool)
            ps, rs = (np.asarray(x, np.float64)[a] for x in
                      (pp["scores"], rp["scores"]))
            sg = max(sg, float(np.max(np.abs(ps - rs) / rs)))
            if step in first:
                pl, rl = (np.asarray(x, np.float64)[a] for x in
                          (pp["loss"], rp["loss"]))
                sq.extend(((pl - rl) / rl) ** 2)
            kept, w = prog["plans"][step]
            sel = max(sel, select_gap(rp["scores"], kept, seed, step))
            wr = race_weights(rp["scores"], kept, seed, step)
            wg = max(wg, float(np.max(np.abs(np.asarray(w) - wr) / wr)))
        out.update(score_gap=sg, pool_loss_gap=float(np.sqrt(np.mean(sq))),
                   select_gap=sel, weight_gap=wg)
    return out


def checks(prog, ref, tr, seed, limits, gate_shut=0):
    g = gaps(prog, ref, tr, seed)
    g["gate_shut_steps"] = gate_shut
    out = []
    for name, limit in limits.items():
        out.append({"name": name, "value": float(g[name]),
                    "limit": float(limit), "ok": g[name] <= limit})
    return out


def same_selection(scores, kept, limit, seed, step):
    return select_gap(scores, kept, seed, step) <= limit
