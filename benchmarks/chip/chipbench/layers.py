"""Per-layer metrics of a traced run.

Each per-layer metric of ``BENCHMARK.json`` has a reader of its own,
``metrics/<name>.py``, with ``read(ctx) -> float | None``; ``None`` (the
reader found nothing to read in this cell) leaves the metric out of the
result line. ``ctx`` carries what a reader may use:

``ctx.trace``     ``tracefile.reduce`` of the traced part of the window,
                  over the cell's chips: ``window_s``, ``busy_s``, device
                  seconds by program (``modules``), by operation (``ops``)
                  and by program and opcode (``by_module``), each a mean
                  over the chips
``ctx.traced_steps``  training steps in the traced part
``ctx.steps``     training steps completed in the whole window
``ctx.window_s``  the whole window's length by the host clock
``ctx.hook``      the run's ``harness.WindowHook`` (per-step gauges,
                  span sums read at the window's edges)
``ctx.chips``     the cell's chips; ``ctx.devices`` their ids, whose
                  planes of the trace are read
``ctx.cfg``, ``ctx.traffic``, ``ctx.device_kind``
"""
from __future__ import annotations

import importlib.util
import json
import types
from pathlib import Path

from chipbench import tracefile

ROOT = Path(__file__).resolve().parent.parent
ANNOTATIONS = ("chipbench.step_dispatch", "chipbench.plane_begin",
               "chipbench.plane_finish")


def annotate_experiment(exp):
    """Host annotations around the loop's calls into each layer, so that
    an idle gap can be attributed to what the host was doing."""
    exp.step_fn = tracefile.annotate("chipbench.step_dispatch", exp.step_fn)
    make_plane = exp.make_plane

    def annotated_plane():
        plane = make_plane()
        plane.begin = tracefile.annotate("chipbench.plane_begin",
                                         plane.begin)
        plane.finish = tracefile.annotate("chipbench.plane_finish",
                                          plane.finish)
        return plane
    exp.make_plane = annotated_plane


def reader(name):
    path = ROOT / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of(workload, bench):
    """The per-layer metrics this cell reports: those that list it, and
    those without a list whose end-to-end metric the cell reports."""
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif "workloads" not in e2e[m["moves"]] \
                or workload in e2e[m["moves"]]["workloads"]:
            out.append(m)
    return out


def per_layer(workload, cfg, tr, hook, tracer, device_kind, bench, *,
              devices):
    summary = tracefile.reduce(tracefile.load(tracer.path), ANNOTATIONS,
                               devices=devices)
    ctx = types.SimpleNamespace(
        trace=summary, steps=hook.window_steps,
        traced_steps=hook.traced_steps,
        window_s=hook.t1 - hook.t0, hook=hook, cfg=cfg, traffic=tr,
        device_kind=device_kind, chips=len(devices), devices=tuple(devices))
    out = {}
    for m in metrics_of(workload, bench):
        v = reader(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    breakdown = {"device_ops": tracefile.top(summary["ops"]),
                 "idle_gaps": tracefile.top(summary["idle_gaps"])}
    return out, breakdown, summary


def load_bench():
    with open(ROOT.parent.parent / "BENCHMARK.json") as f:
        return json.load(f)
