"""The program's spans against the device's idle gaps
(``chipbench/spans.py``) and the four readers that use them.

On hand-made events: an idle gap split between the spans over it, the
loop's thread before a worker's, clipping to the window, the remainder
no span covers, and each reader, also on a trace with no program span.
On a CPU profile: ``load`` keeps each thread's spans apart.
"""
import sys
import threading
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from chipbench import layers, spans, tracefile  # noqa: E402

MS = 1e6


def trace():
    """Window 0..100 ms; the chip busy 0-20, 40-70, 90-100, so idle
    20-40 and 70-90 (40 ms). The loop thread (line 0, with the step
    markers) and one worker thread (line 1)."""
    loop = [("train", -10 * MS, 60 * MS), ("train", 60 * MS, 130 * MS),
            # 20-40: loop.sync 15-30, then loop.emit.step_end 30-38
            # holding a nested loop.emit.checkpoint 32-35
            ("loop.sync", 15 * MS, 30 * MS),
            ("loop.emit.step_end", 30 * MS, 38 * MS),
            ("loop.emit.checkpoint", 32 * MS, 35 * MS),
            # 70-90: sampler.select 72-80; runs past the window's end
            ("sampler.select", 72 * MS, 80 * MS),
            ("loop.emit.step_start", 95 * MS, 120 * MS),
            ("chipbench.plane_finish", 70 * MS, 90 * MS)]
    # the worker's span covers 75-88; inside 72-80 the loop's span wins
    worker = [("plane.gather", 75 * MS, 88 * MS)]
    dev = [(0.0, 20 * MS), (40 * MS, 55 * MS), (50 * MS, 70 * MS),
           (90 * MS, 100 * MS)]
    return {"window": (0.0, 100 * MS), "devices": [dev],
            "lines": [loop, worker]}


def test_split_attribution_and_remainder():
    idle = spans.idle_by_span(trace())
    want = {"loop.sync": 0.010,                    # 20-30
            "loop.emit.step_end": 0.005,           # 30-32, 35-38
            "loop.emit.checkpoint": 0.003,         # 32-35, innermost
            # 38-40, 70-72 and 88-90: under no program span (the
            # benchmark's own annotation is not one)
            "unattributed": 0.006,
            "sampler.select": 0.008,               # 72-80 over the worker
            "plane.gather": 0.008,                 # 80-88
            }
    assert idle == pytest.approx(want)
    assert sum(idle.values()) == pytest.approx(0.040)


def test_loop_thread_before_worker_thread():
    tr = trace()
    # a worker span shorter than the loop's, inside it: still the loop's
    tr["lines"][1] = [("plane.gather", 74 * MS, 76 * MS)]
    idle = spans.idle_by_span(tr)
    assert idle["sampler.select"] == pytest.approx(0.008)
    assert "plane.gather" not in idle
    # without the step markers no line is the loop's: the innermost wins
    tr["lines"][0] = [e for e in tr["lines"][0] if e[0] != "train"]
    idle = spans.idle_by_span(tr)
    assert idle["plane.gather"] == pytest.approx(0.002)
    assert idle["sampler.select"] == pytest.approx(0.006)


def test_clipped_to_the_window():
    tr = trace()
    match = spans.span_matcher(["loop.emit.*"])
    # step_end 30-38 (the nested checkpoint is inside it) + step_start
    # 95-100 of 95-120
    assert spans.host_s(tr, match) == pytest.approx(0.013)
    assert spans.host_s(tr, spans.span_matcher(["sampler.select"])) == \
        pytest.approx(0.008)
    # a span that starts before the window: only its part inside
    tr["lines"][0].append(("loop.dispatch", -50 * MS, 10 * MS))
    assert spans.host_s(tr, spans.span_matcher(["loop.dispatch"])) == \
        pytest.approx(0.010)
    # idle up to the window's end, under a span that runs past it
    tr["devices"][0][-1] = (90 * MS, 92 * MS)
    idle = spans.idle_by_span(tr)
    assert idle["loop.emit.step_start"] == pytest.approx(0.005)  # 95-100
    assert idle["unattributed"] == pytest.approx(0.006 + 0.003)  # + 92-95
    assert sum(idle.values()) == pytest.approx(0.048)


def _ctx(tr, steps=2):
    return types.SimpleNamespace(hook=types.SimpleNamespace(tracer=None),
                                 traced_steps=steps, spans_trace=tr)


def _read(name, ctx):
    return layers.reader(name).read(ctx)


def test_readers():
    ctx = _ctx(trace())
    assert _read("idle.unattributed_ms", ctx) == pytest.approx(6.0 / 2)
    assert _read("idle.loop_sync_ms", ctx) == pytest.approx(10.0 / 2)
    assert _read("loop.hooks_ms", ctx) == pytest.approx(13.0 / 2)
    assert _read("select.host_ms", ctx) == pytest.approx(8.0 / 2)
    t = spans.table(ctx)
    assert t["idle_total_ms"] == pytest.approx(40.0 / 2)
    assert t["host_ms"]["loop.sync"] == pytest.approx(15.0 / 2)


def test_readers_without_the_spans(monkeypatch):
    # the uniform cell: no selection
    tr = trace()
    tr["lines"][0] = [e for e in tr["lines"][0] if e[0] != "sampler.select"]
    ctx = _ctx(tr)
    assert _read("select.host_ms", ctx) is None
    assert _read("idle.loop_sync_ms", ctx) == pytest.approx(5.0)
    # a program without the profiler bridge: the trace holds only the
    # benchmark's own annotations, so every reader finds nothing
    bare = dict(tr, lines=[[e for e in line if e[0].startswith("chipbench.")]
                           for line in tr["lines"]])
    monkeypatch.setattr(spans, "load", lambda path, devices=None: bare)
    for name in ("idle.unattributed_ms", "idle.loop_sync_ms",
                 "loop.hooks_ms", "select.host_ms"):
        ctx = types.SimpleNamespace(
            hook=types.SimpleNamespace(
                tracer=types.SimpleNamespace(path="run.xplane.pb")),
            traced_steps=2)
        assert _read(name, ctx) is None, name


def test_load_keeps_threads_apart(tmp_path):
    import jax
    from repro import obs
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0

    def worker():
        with obs.span("plane.gather"):
            pass

    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tracefile.WINDOW):
            with jax.profiler.StepTraceAnnotation("train", step_num=0):
                with obs.span("loop.sync"):
                    t = threading.Thread(target=worker)
                    t.start()
                    t.join()
    finally:
        jax.profiler.stop_trace()
    (path,) = sorted(tmp_path.rglob("*.xplane.pb"))
    tr = spans.load(path)
    assert tr["window"] is not None
    lines = [sorted(n for n, _, _ in line) for line in tr["lines"] if line]
    assert sorted(lines) == [["loop.sync", "train"], ["plane.gather"]]
    assert spans._steps_in_window(tr) == 1


@pytest.mark.parametrize("workload", ["xlstm-350m.is_on",
                                      "xlstm-350m.uniform"])
def test_traced_small_run_reads_the_program_spans(workload, monkeypatch):
    """A ``--trace 1`` run of each cell at a small size on the CPU: the
    window's trace holds the program's spans, and the readers report
    them (the CPU has no device plane, so no idle is read there, and
    the device's readers, which need a chip, are left out)."""
    from test_chip_harness import SEED, small_cell
    from chipbench import harness
    from repro import obs
    from repro.api.config import apply_overrides
    orig = harness.run_config
    monkeypatch.setattr(harness, "run_config", lambda *a: apply_overrides(
        orig(*a), {"imp.score_dtype": "float32"}))
    bench = layers.load_bench()
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if m["source"] == "program_span"]
    monkeypatch.setattr(layers, "load_bench", lambda: bench)
    was = obs.enabled()
    try:
        result, _ = harness.run(workload, SEED, 0.5, trace=True,
                                cell=small_cell(workload),
                                log=lambda m: None)
    finally:
        obs.reset()
        obs.enable(was)
    m = result["metrics"]
    assert m["loop.hooks_ms"]["value"] > 0
    assert m["idle.unattributed_ms"]["value"] == 0.0
    assert m["idle.loop_sync_ms"]["value"] == 0.0
    assert ("select.host_ms" in m) is workload.endswith("is_on")
    assert m["plane.wait_ms"]["value"] >= 0
    assert result["correct"]


@pytest.mark.parametrize("devices,planes", [(None, 1), ((0,), 1), ((1,), 0)])
def test_load_reads_only_the_cells_chips(devices, planes):
    """The recorded one-chip trace (``testdata/``) holds the plane of chip
    0 alone: read for chip 0 it keeps that plane's operations, read for
    chip 1 none."""
    path = HERE.parent / "testdata" / "v5e_small.xplane.pb"
    tr = spans.load(path, devices)
    assert len(tr["devices"]) == planes
    assert all(tr["devices"])
