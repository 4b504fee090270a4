"""On-chip smoke test of the fused importance-sampling trainer.

    python chip_smoke.py             # one TPU chip
    python chip_smoke.py --chips 4   # one host with four chips

One chip: trains ``xlstm-350m`` at its published widths (d_model 1024,
4 heads, vocab 50304, all 24 layers) for six steps through the normal
launcher (``repro.launch.train.main``) with the ``prod`` preset — fused
device presample, survival-pruned pool scoring, telemetry on. It then
checks that the compiled scoring program holds Pallas kernels
(``tpu_custom_call``) and compares each main-path kernel, on the chip at
the run's shapes, with its ``ref.py`` oracle.

Four chips: the same model's IS train step on a (data=2, model=2) mesh
against the same step on one device, in one process, from the same seed
and batches; losses and parameters are compared after two steps.

Weights are random (seeded) and the data synthetic. The only cuts are the
sequence length and the batch (``CUTS``). Times printed are informational
only. Without a TPU the script exits non-zero and prints no result; on
success its last line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
ARCH = "xlstm-350m"
SEQ, BATCH, RATIO = 2048, 4, 3
SEED = 0
VOCAB = 50304            # xlstm-350m's
CUTS = {  # value, and what the prod preset's train_4k shape has
    "shape.seq_len": (SEQ, "train_4k: 4096"),
    "shape.global_batch": (BATCH, f"train_4k: 256; the pool is {RATIO}x "
                           f"this at presample ratio {RATIO}"),
}
STEPS = 6
# parity tolerances, chosen from f32 accumulation over a 2048-token row
# and the chip's transcendental functions; printed with each check
SCORE_RTOL = 1e-3        # survivor scores / per-row loss, kernel vs oracle
KEY_RTOL = 1e-5          # race keys, kernel vs oracle (same hash, f32 tail)
# sLSTM kernel vs lax.scan, relative L2 per output: both round the
# recurrent products' operands to bf16, and each is ~5e-3 off exact f32
# products in the gradients; the kernel sits ~6e-4 from the scan
SLSTM_RTOL = 2e-3
# mLSTM GLA kernels vs chunked_gla, relative L2 per output: both take the
# products' operands in bf16; the reference also rounds the normaliser
# and the gated value to bf16, the kernel keeps them in f32
MLSTM_RTOL = 2e-2
# four chips: the sharded step rounds bf16 partial sums that one device
# accumulates in f32 (gradients and updates are held to a yardstick
# measured in the run, see sharded_vs_single)
LOSS_RTOL = (2e-3, 2e-2)  # step-1 loss (same weights), step-2 loss


class SmokeFailure(Exception):
    """A phase failed; the message says which check."""


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)


def log(msg=""):
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------
def train_argv():
    obs_dir = REPO / ".smoke_obs"
    return ["--arch", ARCH, "--preset", "prod", f"--steps={STEPS}",
            f"--shape.seq_len={SEQ}", f"--shape.global_batch={BATCH}",
            f"--imp.presample_ratio={RATIO}", f"--seed={SEED}",
            f"--obs.dir={obs_dir}"]


def train(launch, argv):
    """``STEPS`` accepted steps through the launcher; returns the
    experiment and the step records. Fails on a non-finite loss or a
    swallowed hook error (``TrainLoop.emit`` isolates hooks, so only the
    counter says)."""
    from repro import obs
    t0 = time.perf_counter()
    exp, history = launch.main(argv)
    wall = time.perf_counter() - t0
    cfg = exp.run.model
    log(f"model {cfg.name}: d_model {cfg.d_model}, heads {cfg.n_heads}, "
        f"vocab {cfg.vocab_size}, layers {cfg.n_layers}, "
        f"params {cfg.param_count()}, "
        f"presample_impl {exp.run.imp.presample_impl}, "
        f"score_prune {exp.run.imp.score_prune}")
    losses = [h["loss"] for h in history]
    for h in history:
        log(f"step {h['step']}: loss {h['loss']:.6f} wall {h['dt']:.3f} s "
            f"tau {h.get('tau', float('nan')):.4f}")
    log(f"wall: build + {len(history)} steps {wall:.1f} s; first step "
        f"(includes compiling the train step) {history[0]['dt']:.1f} s; "
        f"later steps {[round(h['dt'], 3) for h in history[1:]]} s "
        f"(informational)")
    counters = {n: obs.counter(n).value for n in (
        "loop.hook_errors", "engine.dispatches", "kernels.prune.tiles_total",
        "kernels.prune.blocks_skipped", "kernels.prune.rows_killed")}
    log(f"counters {counters}")
    check(len(history) == STEPS,
          f"expected {STEPS} accepted steps, got {len(history)}")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(counters["loop.hook_errors"] == 0,
          f"loop.hook_errors = {counters['loop.hook_errors']}")
    check(counters["kernels.prune.tiles_total"] > 0,
          "the survival-pruned scoring pass never ran")
    return exp, history


def scoring_program(exp):
    """Compiled text of the run's own pool-scoring program (the engine's
    jit cache entry, lowered again at the run's pool shapes)."""
    import jax.numpy as jnp
    import numpy as np
    B = exp.run.shape.global_batch * exp.run.imp.presample_ratio
    pool = {k: jnp.asarray(v) for k, v in
            exp.source.gather(np.arange(B)).items()}
    fn = exp.engine._fn_pruned(pool, exp.run.shape.global_batch)
    return fn.lower(exp.last_state["params"], pool,
                    jnp.uint32(0)).compile().as_text()


def kernel_parity():
    """Each main-path kernel vs its ``ref.py`` oracle, on device, at the
    run's pool (B rows of T tokens over the model's vocab V, k kept) and a
    score store of n_store rows. Returns the printed lines; raises on a
    miss."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.fused_presample.ops import pruned_pool_score, select_pool
    from repro.kernels.fused_presample.ref import (pruned_pool_score_ref,
                                                   select_pool_ref)
    from repro.kernels.topk_keys.ops import topk_race_keys
    from repro.kernels.topk_keys.ref import topk_race_keys_ref
    from repro.sampler import selection

    B, T, V, k, n_store = BATCH * RATIO, SEQ, VOCAB, BATCH, 1 << 16
    ks = jax.random.split(jax.random.PRNGKey(SEED), 4)
    # rows of very different sharpness, so the race kills some of them
    scale = jnp.linspace(0.5, 4.0, B)[jax.random.permutation(ks[0], B)]
    logits = (jax.random.normal(ks[1], (B, T, V), jnp.bfloat16)
              * scale[:, None, None].astype(jnp.bfloat16))
    labels = jax.random.randint(ks[2], (B, T), 0, V, jnp.int32)
    labels = jnp.where(jax.random.uniform(ks[3], (B, T)) < 0.1, -1, labels)
    ctx = selection.hash_context(SEED, 4211, 1)

    got = pruned_pool_score(logits, labels, ctx, k=k)
    scores, alive, loss_ps, _ = (np.asarray(x) for x in got)
    want = pruned_pool_score_ref(logits, labels, ctx, k=k)
    del logits
    same_alive = np.array_equal(alive, want[1])
    live = alive > 0
    err_s = float(np.max(np.abs(scores - want[0])[live]
                         / np.abs(want[0])[live]))
    err_l = float(np.max(np.abs(loss_ps - want[2])[live]
                         / np.abs(want[2])[live]))
    ok = same_alive and err_s <= SCORE_RTOL and err_l <= SCORE_RTOL
    lines = [f"parity ce_score_block (pruned_pool_score vs "
             f"pruned_pool_score_ref) B={B} T={T} V={V} k={k}: alive mask "
             f"{'equal' if same_alive else 'DIFFERS'} ({int(B - live.sum())}"
             f"/{B} rows killed), survivor score max rel err {err_s:.3e}, "
             f"loss max rel err {err_l:.3e} (tol {SCORE_RTOL:g}): "
             f"{'PASS' if ok else 'FAIL'}"]
    check(ok, lines[-1])

    rng = np.random.default_rng(SEED)
    for n, kk, sc in ((B, k, scores),
                      (64 * B, 16 * k, rng.uniform(0.01, 5.0, 64 * B))):
        sc = jnp.asarray(sc, jnp.float32)
        idx, _, w, _ = (np.asarray(x) for x in select_pool(sc, ctx, k=kk))
        ridx, _, rw, _ = (np.asarray(x)
                          for x in select_pool_ref(sc, ctx, k=kk))
        same = np.array_equal(np.sort(idx), np.sort(ridx))
        w, rw = w[np.argsort(idx)], rw[np.argsort(ridx)]
        err_w = float(np.max(np.abs(w - rw) / rw)) if same else math.inf
        ok = same and err_w <= KEY_RTOL
        lines.append(f"parity select_pool vs select_pool_ref B={n} k={kk}: "
                     f"selected set {'equal' if same else 'DIFFERS'}, "
                     f"weight max rel err {err_w:.3e} (tol {KEY_RTOL:g}): "
                     f"{'PASS' if ok else 'FAIL'}")
        check(ok, lines[-1])

    kk = 64
    sc = rng.uniform(0.05, 6.0, n_store).astype(np.float32)
    seen = (rng.uniform(size=n_store) < 0.8).astype(np.float32)
    dist = selection.GlobalDist(selection.shard_stats(sc, seen, 0.5),
                                n_store, 0.1, 0.5)
    keys, slots = topk_race_keys(jnp.asarray(sc), jnp.asarray(seen),
                                 np.uint32(ctx), dist.fill_pow, dist.total,
                                 k=kk, n_global=n_store, smoothing=0.1,
                                 inv_temp=2.0)
    r = np.asarray(topk_race_keys_ref(
        sc, seen, np.arange(n_store, dtype=np.uint32), ctx,
        fill_pow=dist.fill_pow, total=dist.total, n_global=n_store,
        smoothing=0.1, inv_temp=2.0))
    slots, keys = np.asarray(slots), np.asarray(keys)
    same = np.array_equal(np.sort(slots),
                          np.sort(np.argsort(r, kind="stable")[:kk]))
    err_k = float(np.max(np.abs(keys - r[slots]) / r[slots]))
    ok = same and err_k <= KEY_RTOL
    lines.append(f"parity race_keys (topk_race_keys vs topk_race_keys_ref) "
                 f"n={n_store} k={kk}: selected set "
                 f"{'equal' if same else 'DIFFERS'}, key max rel err "
                 f"{err_k:.3e} (tol {KEY_RTOL:g}): {'PASS' if ok else 'FAIL'}")
    check(ok, lines[-1])
    lines.append(slstm_parity())
    lines.append(mlstm_gla_parity())
    return lines


def slstm_parity():
    """The sLSTM recurrence kernel (``slstm_scan``) against its
    ``lax.scan`` oracle at xlstm-350m's width over the trained rows:
    outputs, final state, and one VJP (g_in, R, initial state). R at the
    model's init scale. Returns the printed line; raises on a miss."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.slstm.ops import slstm_scan
    from repro.kernels.slstm.ref import slstm_scan_ref

    b, s, d, nh = BATCH, SEQ, 1024, 4
    hd = d // nh
    ks = jax.random.split(jax.random.PRNGKey(SEED), 4)
    g = jax.random.normal(ks[0], (b, s, 4 * d))
    r = 0.05 * jax.random.truncated_normal(ks[1], -2.0, 2.0,
                                           (nh, hd, 4 * hd))
    state0 = (jnp.zeros((b, d)), jnp.ones((b, d)), jnp.zeros((b, d)))
    ct = jax.random.normal(ks[2], (b, s, d))

    def run(scan):
        out, vjp = jax.vjp(scan, g, r, state0)
        return out, vjp((ct, jax.tree.map(jnp.ones_like, out[1])))
    got = jax.jit(lambda: run(slstm_scan))()
    want = jax.jit(lambda: run(slstm_scan_ref))()
    names = ["hs", "c", "n", "h", "dg_in", "dR", "dc0", "dn0", "dh0"]
    errs = {k: float(jnp.linalg.norm(x - y) / jnp.linalg.norm(y))
            for k, x, y in zip(names, jax.tree.leaves(got),
                               jax.tree.leaves(want))}
    ok = all(e <= SLSTM_RTOL for e in errs.values())
    line = (f"parity slstm (slstm_scan vs slstm_scan_ref, forward and VJP) "
            f"b={b} s={s} d={d} heads={nh}: rel L2 err "
            + ", ".join(f"{k} {e:.3e}" for k, e in errs.items())
            + f" (tol {SLSTM_RTOL:g}): {'PASS' if ok else 'FAIL'}")
    check(ok, line)
    return line


def mlstm_gla_parity():
    """The mLSTM's GLA kernels (``mlstm_gla``) against ``chunked_gla``
    (``mlstm_gla_ref``) at xlstm-350m's widths over the trained rows, q, k
    and v in bf16 as the model gives them: y, the normaliser, and one VJP
    (q, k, v, input gate, log forget gate). Returns the printed line;
    raises on a miss."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.mlstm_gla.ops import mlstm_gla
    from repro.kernels.mlstm_gla.ref import mlstm_gla_ref

    b, s, nh, hd, chunk = BATCH, SEQ, 4, 512, 256
    ks = jax.random.split(jax.random.PRNGKey(SEED), 6)
    qkv = [(jax.random.normal(kk, (b, s, nh, hd)) * sc).astype(jnp.bfloat16)
           for kk, sc in zip(ks[:3], (hd ** -0.5, hd ** -0.5, 1.0))]
    i_g = jax.nn.sigmoid(jax.random.normal(ks[3], (b, s, nh)))
    log_f = jax.nn.log_sigmoid(jax.random.normal(ks[4], (b, s, nh)) + 3.0)
    ct = jax.random.normal(ks[5], (b, s, nh, hd)).astype(jnp.bfloat16)

    def run(op):
        (y, n), vjp = jax.vjp(lambda *a: op(*a, chunk), *qkv, i_g, log_f)
        return (y, n), vjp((ct, jnp.ones_like(n)))
    got = jax.jit(lambda: run(mlstm_gla))()
    want = jax.jit(lambda: run(mlstm_gla_ref))()
    f32 = lambda x: x.astype(jnp.float32)
    names = ["y", "n", "dq", "dk", "dv", "di", "dlog_f"]
    errs = {k: float(jnp.linalg.norm(f32(x) - f32(y))
                     / jnp.linalg.norm(f32(y)))
            for k, x, y in zip(names, jax.tree.leaves(got),
                               jax.tree.leaves(want))}
    ok = all(e <= MLSTM_RTOL for e in errs.values())
    line = (f"parity mlstm_gla (mlstm_gla vs mlstm_gla_ref, forward and VJP) "
            f"b={b} s={s} heads={nh}x{hd} chunk={chunk}: rel L2 err "
            + ", ".join(f"{k} {e:.3e}" for k, e in errs.items())
            + f" (tol {MLSTM_RTOL:g}): {'PASS' if ok else 'FAIL'}")
    check(ok, line)
    return line


def one_chip(launch, jax):
    log(f"TPU_WORKER_HOSTNAMES={os.environ.get('TPU_WORKER_HOSTNAMES')!r}; "
        f"cluster: {launch.cluster_env() or 'none, no jax.distributed'}")
    check(launch.cluster_env() is None,
          f"this launch is one process of a cluster ({launch.cluster_env()}); "
          f"the smoke runs on one host")
    log("cuts: " + "; ".join(f"{k}={v} ({why})"
                             for k, (v, why) in CUTS.items()))
    exp, _ = train(launch, train_argv())
    text = scoring_program(exp)
    n_kernels = text.count("tpu_custom_call")
    log(f"scoring program: {n_kernels} tpu_custom_call sites")
    check(n_kernels > 0, "the compiled scoring program has no Pallas kernel")
    stats = jax.devices()[0].memory_stats()
    peak = stats["peak_bytes_in_use"]
    log(f"peak device memory after training: {peak} B ({peak / 2**30:.2f} "
        f"GiB); memory_stats {stats}")
    del exp
    gc.collect()
    for line in kernel_parity():
        log(line)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def two_steps(exp, state, batches):
    """The experiment's train step on each batch, from ``state`` (host
    arrays, put on the experiment's layout). Returns each step's (loss,
    grad norm), and on host the step-1 first moment — AdamW's gradient
    EMA, (1 − b1)·g — and the master weights after the last step."""
    import jax
    import jax.numpy as jnp
    state = jax.device_put(state, exp.state_shardings or jax.devices()[0])
    metrics, m1 = [], None
    for batch in batches:
        state, out = exp.step_fn(state, batch, jnp.float32(1.0))
        metrics.append((float(out["loss"]), float(out["grad_norm"])))
        if m1 is None:
            m1 = jax.device_get(state["opt"]["m"])
    return metrics, m1, jax.device_get(state["opt"]["master"])


def one_ulp_away(state, seed=SEED):
    """``state`` with every bf16 weight moved one unit in the last place,
    up or down at random, and its f32 master copy moved with it."""
    import jax
    import numpy as np
    rng = np.random.default_rng(seed)

    def bump(p):
        p = np.asarray(p)
        if p.dtype.name != "bfloat16":
            return p
        bits = p.view(np.uint16).astype(np.int32)   # sign | magnitude
        down = (rng.random(p.shape) < 0.5) & ((bits & 0x7FFF) > 0)
        return (bits + np.where(down, -1, 1)).astype(np.uint16).view(p.dtype)

    params = jax.tree_util.tree_map(bump, state["params"])
    master = jax.tree_util.tree_map(lambda p: np.asarray(p, np.float32),
                                    params)
    return {**state, "params": params, "opt": {**state["opt"],
                                               "master": master}}


def sharded_vs_single(run, mesh):
    """The IS step on ``mesh`` vs on one device, from the same state and
    batches; returns the printed lines, raises on a miss.

    bf16 matmuls partitioned over the model axis round partial sums that
    one device accumulates in f32, so the two agree to rounding, not
    bitwise; in f32 they agree to ~1e-6. The xLSTM's gradient at a random
    init is so sensitive to rounding that the step-1 gradients differ
    elementwise by far more than their norms do. So the yardstick is
    measured here: the one-device step from the same state with every
    bf16 weight moved by one ulp. The sharded step's gradient, and its
    two-step update of the master weights, must lie closer to the
    one-device step's than that run's do. Losses are compared relatively."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.api import Experiment

    single = Experiment(run)
    b = run.shape.global_batch
    batches = []
    for i in range(2):
        rows = single.source.gather(np.arange(i * b, (i + 1) * b))
        rows = {k: jnp.asarray(v) for k, v in rows.items()}
        rows["weights"] = jnp.linspace(0.5, 1.5, b, dtype=jnp.float32)
        batches.append(rows)
    init = jax.device_get(single.init_state()[0])
    moved = one_ulp_away(init)
    one = two_steps(single, init, batches)
    ulp = two_steps(single, moved, batches)
    del single
    gc.collect()
    many = two_steps(Experiment(run, mesh=mesh), init, batches)

    flat = lambda t: np.concatenate([np.ravel(np.asarray(x, np.float32))
                                     for x in jax.tree_util.tree_leaves(t)])
    gap = lambda a, c: float(np.linalg.norm(a - c) / np.linalg.norm(c))
    g_one, g_ulp, g_many = flat(one[1]), flat(ulp[1]), flat(many[1])
    grad_gap, grad_tol = gap(g_many, g_one), gap(g_ulp, g_one)
    per_leaf = sorted(
        ((gap(np.asarray(a, np.float32), np.asarray(c, np.float32)),
          jax.tree_util.keystr(path))
         for (path, a), c in zip(
             jax.tree_util.tree_flatten_with_path(many[1])[0],
             jax.tree_util.tree_leaves(one[1]))), reverse=True)
    w0 = flat(init["opt"]["master"])
    d_one, d_many = flat(one[2]) - w0, flat(many[2]) - w0
    d_ulp = flat(ulp[2]) - flat(moved["opt"]["master"])
    upd_gap, upd_tol = gap(d_many, d_one), gap(d_ulp, d_one)
    flips = float(np.mean(np.sign(d_many) != np.sign(d_one)))

    loss = lambda r: [m[0] for m in r[0]]
    loss_err = [abs(m - o) / abs(o) for m, o in zip(loss(many), loss(one))]
    ok = (loss_err[0] <= LOSS_RTOL[0] and loss_err[1] <= LOSS_RTOL[1]
          and grad_gap <= grad_tol and upd_gap <= upd_tol)
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    lines = [
        f"mesh {shape} vs one device, same state and batches: losses "
        f"{loss(many)} vs {loss(one)} (rel err {loss_err[0]:.3e}, "
        f"{loss_err[1]:.3e}; tol {LOSS_RTOL[0]:g}, {LOSS_RTOL[1]:g}); one "
        f"device one ulp away: {loss(ulp)}",
        f"grad norms {[m[1] for m in many[0]]} vs {[o[1] for o in one[0]]}",
        f"step-1 gradient (AdamW first moment): ‖g_mesh − g_one‖/‖g_one‖ = "
        f"{grad_gap:.3e} (tol {grad_tol:.3e} = the same for one device one "
        f"ulp away); worst leaves "
        f"{[(k, f'{e:.2e}') for e, k in per_leaf[:3]]}",
        f"master weights after 2 steps: ‖Δ_mesh − Δ_one‖/‖Δ_one‖ = "
        f"{upd_gap:.3e} (tol {upd_tol:.3e} = the same for one device one "
        f"ulp away); {flips:.2%} of updates moved the other way",
        f"sharded vs one device: {'PASS' if ok else 'FAIL'}"]
    check(ok, " / ".join(lines))
    return lines


def four_chips(launch, jax):
    from repro.api.config import build_run
    from repro.launch.mesh import make_host_mesh
    check(len(jax.devices()) == 4,
          f"--chips 4 needs four devices, JAX sees {len(jax.devices())}")
    log("cuts: " + "; ".join(f"{k}={v} ({why})"
                             for k, (v, why) in CUTS.items()))
    run = build_run(arch=ARCH, preset="prod", overrides={
        "shape.seq_len": SEQ, "shape.global_batch": BATCH,
        "imp.presample_ratio": RATIO, "seed": SEED})
    t0 = time.perf_counter()
    for line in sharded_vs_single(run, make_host_mesh(model=2)):
        log(line)
    log(f"wall {time.perf_counter() - t0:.1f} s (informational)")
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]
    log(f"peak device memory per chip: {peaks} B")


# ---------------------------------------------------------------------------
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    src = REPO / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: FAIL: no repro package under {src}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(src))
    from repro.launch import train as launch
    cache = launch.configure_compile_cache()
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: FAIL: JAX found no devices: {e}", file=sys.stderr)
        return 1
    log(f"jax {jax.__version__}; devices {devices}; compile cache {cache}")
    if devices[0].platform != "tpu":
        print(f"chip_smoke: FAIL: no TPU found (JAX runs on "
              f"{devices[0].platform})", file=sys.stderr)
        return 1
    try:
        (four_chips if args.chips == 4 else one_chip)(launch, jax)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
