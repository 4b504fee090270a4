"""One run of one cell: set-up, the measured window, the checks.

The system under test is the program's normal training path:
``repro.api.Experiment`` built from the ``prod`` preset, trained by
``Experiment.fit`` (the event-hook ``TrainLoop`` with the preset's hooks),
which takes in turn the data plane, fused presample scoring with
survival pruning, host selection and the donated train step. The
benchmark gives it three things: the configuration (``configs/``), the
traffic (``workloads/``, made into rows by ``traffic.Rows`` and passed as
the run's data source) and the initial weights (``reference.make_params``,
so that the reference starts from the same ones). A cell of several chips
has its (data, model) mesh in the configuration's ``mesh`` key; the
program is built on it as its launcher builds it for ``--mesh``, the
weights are made on the program's own shardings, and the reference is
laid out over the same chips (``reference.Placement``).

A run:

1. set-up: build the experiment, make the weights, and train the first
   ``CHECK_STEPS`` steps through the same ``fit`` call the window uses.
   They compile every program the window runs; the hook reads the
   numbers the reference is compared with as they go by (each step's
   loss, the first gradient from the optimizer's state after one step,
   the parameters' change after the last; the pool scores and the
   selection of every step);
2. the window: the same loop goes on for ``seconds``; the hook counts
   steps and ends the loop at the first step that completes after the
   window's length has passed;
3. after the window: the device's peak memory; the probe loss, the
   reference forward on the trained weights; the program's state is
   freed; the reference replays the checked steps and ``compare``
   decides ``correct``.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, reference, traffic

ROOT = Path(__file__).resolve().parent.parent      # benchmarks/chip
CHECK_STEPS = 3
TRACE_STEPS = 1            # window steps a --trace 1 run records


class WindowClosed(BaseException):
    """Raised by the window's hook to end ``fit`` once the window has
    passed. ``TrainLoop`` isolates hook errors of type ``Exception`` (they
    are counted and the run goes on), so the stop is a ``BaseException``;
    the loop's ``finally`` still stops the data plane."""


# ---------------------------------------------------------------------------
# cell → program configuration
# ---------------------------------------------------------------------------
def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(workload, bench_path=None):
    bench = load_json(bench_path or ROOT.parent.parent / "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise SystemExit(f"unknown workload {workload!r}")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(ROOT.parent.parent / conf["file"])
    mesh_shape(cell, cfg)
    return cell, cfg, traffic.load(cell["traffic"]), \
        load_json(ROOT / "limits" / f"{workload}.json")


def mesh_shape(cell, cfg):
    """The cell's (data, model) mesh from its configuration's ``mesh``
    key; None for a one-chip cell or a configuration without one. A mesh
    of another number of chips than the cell's is an error."""
    mesh = cfg.get("mesh")
    if mesh is None:
        return None
    shape = int(mesh["data"]), int(mesh["model"])
    if shape[0] * shape[1] != int(cell["chips"]):
        raise SystemExit(f"{cell['name']}: the mesh {mesh} is not of the "
                         f"cell's {cell['chips']} chip(s)")
    return None if cell["chips"] == 1 else shape


def model_config(cfg):
    from repro.configs.base import ModelConfig, Segment, SSMConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in cfg.items() if k in fields
          and k not in ("segments", "ssm")}
    return ModelConfig(
        segments=tuple(Segment(tuple(s["pattern"]), int(s["repeats"]))
                       for s in cfg["segments"]),
        ssm=SSMConfig(**cfg["ssm"]), **kw)


def run_config(cfg, tr, seed):
    from repro.api.config import build_run
    o = tr["optim"]
    overrides = {
        "shape.seq_len": tr["seq_len"],
        "shape.global_batch": tr["global_batch"],
        "imp.presample_ratio": tr["presample_ratio"],
        "imp.tau_th": tr["tau_th"],
        "sampler.scheme": tr["scheme"],
        "optim.name": "adamw", "optim.lr": o["lr"], "optim.b1": o["b1"],
        "optim.b2": o["b2"], "optim.eps": o["eps"],
        "optim.weight_decay": o["weight_decay"],
        "optim.grad_clip": o["grad_clip"],
        "obs.sink": "none",
        "steps": 1 << 30,
        "seed": int(seed),
    }
    return build_run(model=model_config(cfg), preset="prod",
                     overrides=overrides)


def make_source(rows):
    """The traffic's rows as the program's data source."""
    from repro.data.pipeline import DataSource

    class Source(DataSource):
        def gather(self, indices, epoch=0):
            return rows.gather(indices)
    return Source(rows.n, host_id=0, n_hosts=1)


# ---------------------------------------------------------------------------
# the hook: checked steps, window, per-step readings
# ---------------------------------------------------------------------------
class WindowHook:
    """Observes ``fit``: reads the checked steps, times the window and
    ends it."""

    def __init__(self, cfg, seed, seconds, *, tracer=None, b1=0.9,
                 clip=1.0, param_shardings=None):
        from repro import obs
        self.cfg, self.seed = cfg, seed
        self.param_shardings = param_shardings
        self.seconds = float(seconds)
        self.tracer = tracer
        self.b1, self.clip = b1, clip
        self.loop = None
        self.losses = []            # program loss of each checked step
        self.gnorms = []            # its gradient's global norm (pre-clip)
        self.plans = []             # (src_rows, weights) per checked step
        self.grad_norms = None      # per leaf, first gradient
        self.grad_sketch = None     # its random projections
        self.embed_sq = None        # its embedding rows' squared norms
        self.row_scores = {}        # per checked step: the step's row Ĝ
        self.update_norms = None    # per leaf, change after CHECK_STEPS
        self.window_steps = 0
        self.traced_steps = 0
        self.t0 = self.t1 = None
        self.gate_shut = 0
        self.taus = []
        self.plane_wait0 = None
        self.plane_wait1 = None
        self._wait = obs.span("plane.next_wait")
        self._tau = obs.gauge("health.tau")

    # Hook protocol (duck-typed: the loop calls on_<event> on each hook)
    def __getattr__(self, name):
        if name.startswith("on_"):
            return lambda *a, **k: None
        raise AttributeError(name)

    def on_loop_start(self, loop, start, steps):
        self.loop = loop
        self.marks = {"loop_start": time.perf_counter()}

    def on_step_start(self, loop, step, batch, plan):
        if step == 0:
            self.marks["step0_start"] = time.perf_counter()
        if step < CHECK_STEPS:
            w = getattr(plan, "weights", None)
            src = getattr(plan, "src_rows", None)
            self.plans.append((None if src is None else np.asarray(src),
                               None if w is None else np.asarray(w,
                                                                 np.float64)))

    def on_scores_ready(self, loop, step, plan, scores):
        if step < CHECK_STEPS:
            self.row_scores[int(step)] = np.asarray(scores, np.float64)

    def on_step_end(self, loop, step, metrics):
        if step < CHECK_STEPS:
            self.marks[f"step{step}_end"] = time.perf_counter()
            self.losses.append(float(metrics["loss"]))
            self.gnorms.append(float(metrics["grad_norm"]))
            st = loop.state
            if step == 0:
                m = reference.leaf_norms(st["opt"]["m"])
                self.grad_norms = {k: v / (1.0 - self.b1)
                                   for k, v in m.items()}
                self.grad_sketch = [x / (1.0 - self.b1) for x in
                                    reference.sketch(st["opt"]["m"])]
                unclip = max(1.0, self.gnorms[0] / self.clip) \
                    / (1.0 - self.b1)
                self.embed_sq = reference.row_sq(
                    st["opt"]["m"]["embed"]) * unclip ** 2
                self.marks["step0_read"] = time.perf_counter()
            if step == CHECK_STEPS - 1:
                p0 = reference.make_params(self.cfg, self.seed,
                                           self.param_shardings)
                self.update_norms = reference.diff_norms(
                    st["opt"]["master"], p0)
                del p0
                self._start_window()
            return
        self.window_steps += 1
        if float(metrics.get("is_active", 1.0)) < 0.5:
            self.gate_shut += 1
        self.taus.append(float(self._tau.value))
        done = time.perf_counter() - self.t0 >= self.seconds
        if self.tracer is not None and not self.tracer.closed:
            # the profiler runs from the window's start; the traced part
            # is the TRACE_STEPS steps after the first
            if self.window_steps == 1:
                self.tracer.open()
            if self.window_steps == 1 + TRACE_STEPS or done:
                self.traced_steps = self.window_steps - 1
                self.tracer.close()
        if done:
            self.t1 = time.perf_counter()
            self.plane_wait1 = self._wait_sum()
            raise WindowClosed()

    def _wait_sum(self):
        return float(self._wait.snapshot()["sum"])

    def _start_window(self):
        self.plane_wait0 = self._wait_sum()
        if self.tracer is not None:
            self.tracer.start()
        self.t0 = time.perf_counter()


class PoolRecorder:
    """Keeps the device score vectors of the checked steps' pools: wraps
    the sampler's ``begin_finalize`` (where the fused path dispatches its
    scoring) and holds on to what it returned."""

    def __init__(self, sampler):
        self.futs = {}
        orig = sampler.begin_finalize

        def begin_finalize(cplan, pool, cursor, params=None):
            handle = orig(cplan, pool, cursor, params=params)
            if cplan.step < CHECK_STEPS:
                self.futs[int(cplan.step)] = (np.asarray(cplan.gids),
                                              handle["fut"])
            return handle
        sampler.begin_finalize = begin_finalize

    def read(self):
        out = {}
        for step, (gids, fut) in sorted(self.futs.items()):
            loss, scores, alive = (np.asarray(jax.device_get(x), np.float64)
                                   for x in fut[:3])
            out[step] = {"gids": gids, "loss": loss, "scores": scores,
                         "alive": alive > 0.5}
        return out


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------
def build(cfg, tr, seed, marks=None, mesh=None, devices=None):
    """The experiment of one run, with its data source and weights.
    ``marks`` (a dict) gets the clock at the end of each phase. ``mesh``
    (data, model) builds the program on those ``devices`` as its
    launcher's ``--mesh`` does."""
    marks = {} if marks is None else marks
    from repro.api.experiment import Experiment
    from repro.core.is_train import train_state_init
    from repro.data.pipeline import PipelineState
    marks["program_imported"] = time.perf_counter()
    run = run_config(cfg, tr, seed)
    rows = traffic.Rows(tr, cfg["vocab_size"], seed)
    if mesh is not None:
        from repro.launch.mesh import (build_mesh, choose_microbatches,
                                       data_parallel_size)
        mesh = build_mesh(mesh, ("data", "model"), devices=devices)
        run = dataclasses.replace(run, microbatches=choose_microbatches(
            run.model, data_parallel_size(mesh), run.shape.global_batch))
    exp = Experiment(run, source=make_source(rows), mesh=mesh)
    marks["experiment"] = time.perf_counter()
    check_layout(exp, cfg)
    marks["layout_checked"] = time.perf_counter()

    def init_state():
        sh = exp.state_shardings
        if sh is None:
            params = reference.make_params(cfg, seed)
            return train_state_init(exp.lm, exp.opt, None, params=params), \
                PipelineState()
        params = reference.make_params(cfg, seed, sh["params"])
        init = jax.jit(lambda p: train_state_init(exp.lm, exp.opt, None,
                                                  params=p),
                       out_shardings=sh, donate_argnums=0)
        return init(params), PipelineState()
    exp.init_state = init_state
    return exp, rows


def check_layout(exp, cfg):
    """The benchmark's weights must have the program's tree, shapes and
    dtypes, leaf for leaf."""
    want = jax.eval_shape(exp.lm.init, jax.random.PRNGKey(0))
    have = reference.param_shapes(cfg)
    fw = jax.tree_util.tree_flatten_with_path(want)[0]
    fh = jax.tree_util.tree_flatten_with_path(have)[0]
    a = {jax.tree_util.keystr(p): (x.shape, str(x.dtype)) for p, x in fw}
    b = {jax.tree_util.keystr(p): (x.shape, str(x.dtype)) for p, x in fh}
    if a != b:
        diff = sorted(set(a.items()) ^ set(b.items()))[:6]
        raise RuntimeError(f"weights layout differs from the program's: "
                           f"{diff}")


def train(exp, cfg, seed, seconds, *, tracer=None, fault=None):
    """Set-up steps + window through ``Experiment.fit``. Returns the hook
    (readings, window) and the recorder of the checked pools."""
    sh = exp.state_shardings
    hook = WindowHook(cfg, seed, seconds, tracer=tracer, b1=exp.run.optim.b1,
                      clip=exp.run.optim.grad_clip,
                      param_shardings=None if sh is None else sh["params"])
    rec = PoolRecorder(exp.sampler) \
        if hasattr(exp.sampler, "begin_finalize") else None
    if fault is not None:
        fault(exp, seed)
    from repro import obs
    errors = obs.counter("loop.hook_errors")
    errors0 = errors.value
    try:
        exp.fit(hooks=[hook])
    except WindowClosed:
        pass
    if hook.t1 is None:
        raise RuntimeError("the training loop ended before the window did")
    if errors.value != errors0 or hook.update_norms is None:
        raise RuntimeError(f"{errors.value - errors0} hook error(s) in the "
                           f"run; see the loop's report above")
    return hook, rec


def program_readings(hook, rec):
    return {"losses": hook.losses, "gnorms": hook.gnorms, "plans": hook.plans,
            "grad_norms": hook.grad_norms, "grad_sketch": hook.grad_sketch,
            "embed_sq": hook.embed_sq,
            "row_scores": [hook.row_scores.get(s) for s in range(CHECK_STEPS)],
            "update_norms": hook.update_norms,
            "pools": rec.read() if rec is not None else {}}


def probe_loss(params, cfg, rows, place=None):
    """Mean CE of the held-out probe rows under the given (trained)
    weights, by the float32 reference (laid out by ``place``, if any)."""
    data = rows.gather(rows.probe_ids())
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    if place is not None:
        params = jax.device_put(params, place.tree(params))
    ce, _ = reference.pool_stats(params, data["tokens"], data["labels"], cfg,
                                 place=place)
    return float(np.mean(ce))


def reference_readings(cfg, tr, seed, rows, plans, limits, mode="f32",
                       half_batch=False, place=None):
    """The reference replays the checked steps from the same weights:
    scores each step's pool with the weights that step's pool was scored
    with (one step stale, as the program's loop dispatches it), selects,
    and trains on the selection with AdamW. ``plans`` are the program's
    selected pool rows per step: where the reference's race agrees with
    them within ``compare``'s selection limit, the reference trains on
    them, so that a near-tie cannot split the two runs.

    ``mode="fp8"`` is the control (``numerics``); ``half_batch`` plants
    a fault: each step trains on the first half of its rows only, the
    mean taken over them. ``place`` (``reference.Placement``) lays the
    weights, moments, gradients and rows over a cell's chips."""
    o = tr["optim"]
    opt = dict(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
               wd=o["weight_decay"], clip=o["grad_clip"], place=place)
    b = int(tr["global_batch"])
    where = None if place is None else place.tree(reference.param_shapes(cfg))

    def initial():
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                      reference.make_params(cfg, seed, where))
    params = initial()
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    out = {"losses": [], "gnorms": [], "pools": {}, "grad_norms": None,
           "row_scores": []}
    presample = tr["scheme"] == "presample"

    def score(step, weights):
        ids = traffic.pool_ids(tr, step)
        data = rows.gather(ids)
        ce, sc = reference.pool_stats(weights, data["tokens"],
                                      data["labels"], cfg, mode, place)
        return {"gids": ids, "loss": ce, "scores": sc, "data": data}

    # the program's loop scores pool k+1 with the weights before update k
    # (pool 0 with the first ones), so the reference does the same
    nxt = score(0, params) if presample else None
    for step in range(CHECK_STEPS):
        if presample:
            pool = nxt
            if step + 1 < CHECK_STEPS:
                nxt = score(step + 1, params)
            data, sc = pool.pop("data"), pool["scores"]
            sel = compare.race(sc, b, seed, step)
            prog = plans[step][0] if plans else None
            agree = prog is not None and compare.same_selection(
                sc, prog, limits.get("select_gap", 0.0), seed, step)
            rows_used = np.asarray(prog) if agree else sel["idx"]
            w = compare.race_weights(sc, rows_used, seed, step)
            out["pools"][step] = dict(pool, selected=sel["idx"], weights=w,
                                      rows_used=rows_used)
            tok, lab = data["tokens"][rows_used], data["labels"][rows_used]
        else:
            data = rows.gather(traffic.pool_ids(tr, step))
            tok, lab = data["tokens"][:b], data["labels"][:b]
            w = np.ones((b,), np.float64)
        if half_batch:
            tok, lab, w = tok[:b // 2], lab[:b // 2], w[:b // 2]
        loss, grad, rs = reference.loss_and_grad(params, tok, lab, w, cfg,
                                                 mode, row_scores=True,
                                                 place=place)
        out["row_scores"].append(rs)
        if step == 0:
            # the rows' own tokens, and the raw gradient's embedding rows
            out["owner"] = compare.owner_masks(
                [np.concatenate([t, l]) for t, l in zip(tok, lab)],
                cfg["vocab_size"])
            out["embed_sq"] = reference.row_sq(grad["embed"])
        params, m, v, g, gn = reference.adamw_step(
            params, m, v, grad, jnp.float32(step), **opt)
        out["gnorms"].append(float(gn))
        del grad
        if step == 0:
            out["grad_norms"] = reference.leaf_norms(g)
            out["grad_sketch"] = reference.sketch(g)
        del g
        out["losses"].append(loss)
    out["update_norms"] = reference.diff_norms(params, initial())
    return out


def peak_bytes(devices):
    """Each device's peak memory in use, where the backend reports it."""
    out = []
    for d in devices:
        try:
            out.append(int(d.memory_stats()["peak_bytes_in_use"]))
        except Exception:
            out.append(0)
    return out


def device_info(devices):
    """The host's devices as JAX reports them, and the peak memory of the
    fullest of the cell's ``devices``."""
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": max(peak_bytes(devices))}


def run(workload, seed, seconds, *, trace=False, fault=None, cell=None,
        t_start=None, marks=None, log=print):
    """One run of ``workload``. Returns the result dict (the last line)
    and a list of (name, value, limit) for every number compared.
    ``marks``: the clock at the end of the caller's own set-up phases."""
    t_start = time.perf_counter() if t_start is None else t_start
    marks = dict(marks or {})
    if cell is None:
        cell_e, cfg, tr, limits = load_cell(workload)
    else:
        cell_e, cfg, tr, limits = cell
    tracer = None
    if trace:
        from chipbench import tracefile
        tracer = tracefile.Tracer(ROOT / ".out" / "trace" / workload)
    # the cell's chips: the host's first ``chips`` devices
    shape, devices = mesh_shape(cell_e, cfg), jax.devices()[:cell_e["chips"]]
    place = None if shape is None else reference.Placement(devices, *shape)
    exp, rows = build(cfg, tr, seed, marks, mesh=shape, devices=devices)
    if trace:
        from chipbench import layers
        layers.annotate_experiment(exp)
    hook, rec = train(exp, cfg, seed, seconds, tracer=tracer, fault=fault)
    setup_s = hook.t0 - t_start
    marks.update(hook.marks, window_start=hook.t0)
    log("set-up phases (s from start): " + ", ".join(
        f"{k} {v - t_start:.3f}" for k, v in marks.items()))
    window_s = hook.t1 - hook.t0
    b, T = int(tr["global_batch"]), int(tr["seq_len"])
    dev = device_info(devices)
    log(f"peak bytes in use by chip: {peak_bytes(devices)}")
    prog = program_readings(hook, rec)
    # the probe loss reads the trained weights; then the program's state
    # goes, so that the reference has the chips to itself
    pl = probe_loss(hook.loop.state["params"], cfg, rows, place)
    name = exp.run.model.name
    hook.loop = None
    del exp, rec
    gc.collect()
    ref = reference_readings(cfg, tr, seed, rows, prog["plans"], limits,
                             place=place)
    checks = compare.checks(prog, ref, tr, seed, limits,
                            gate_shut=hook.gate_shut)
    for k, v in compare.gaps(prog, ref, tr, seed).items():
        log(f"reading {k}: {v}")
    correct = all(c["ok"] for c in checks)
    if trace:
        metrics, breakdown, summary = layers.per_layer(
            workload, cfg, tr, hook, tracer, dev["kind"], layers.load_bench(),
            devices=[d.id for d in devices])
        dev.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        log(f"trace planes read: {summary['planes']}")
    else:
        metrics = {
            "tokens_per_s": {"value": b * T * hook.window_steps / window_s,
                             "unit": "tokens/s"},
            "probe_loss": {"value": pl, "unit": "nats"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        breakdown = None
    result = {"correct": bool(correct), "attempted": hook.window_steps,
              "failed": 0, "metrics": metrics,
              "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c["name"]: {"value": c["value"],
                                    "limit": c["limit"]} for c in checks}
    log(f"window: {hook.window_steps} steps in {window_s:.3f} s; set-up "
        f"{setup_s:.3f} s; probe loss {pl:.6f}; run {name}")
    return result, checks
