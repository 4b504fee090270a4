"""``repro.obs`` spans in a JAX profiler trace, on the CPU.

A span is also a ``jax.profiler.TraceAnnotation`` while a profiler
session collects, so it lands on the ``.xplane.pb`` host plane on the
device trace's clock. Pinned here: the bridge itself (threads, nesting,
sessions that start or stop mid-span, exceptions, a disabled registry
reading no clock), a profiled fused-presample ``Experiment.fit`` carrying
its ``train`` step markers and the loop's and sampler's spans, and the
names of the compiled programs the chip benchmark's readers match.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs import registry as obs_registry
from repro.obs.registry import Registry


@pytest.fixture(autouse=True)
def _global_registry_guard():
    yield
    obs.enable(False)
    obs.reset()


def _start(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)


def _host_events(tmp_path):
    """[(line index, name, start_ns, end_ns, stats)] of every host event
    in the trace written under ``tmp_path``; a line is one thread (stats
    are read for the step markers only)."""
    from jax.profiler import ProfileData
    (path,) = sorted(tmp_path.rglob("*.xplane.pb"))
    out = []
    pd = ProfileData.from_file(str(path))
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                stats = dict(e.stats) if e.name == "train" else {}
                out.append((li, e.name, e.start_ns,
                            e.start_ns + e.duration_ns, stats))
    return out


def _named(events, name):
    return [e for e in events if e[1] == name]


def _stack(span):
    return getattr(span._local, "stack", None) or []


# ---------------------------------------------------------------------------
# the bridge
# ---------------------------------------------------------------------------
def test_span_appears_in_profiler_trace(tmp_path):
    r = Registry(enabled=False)
    s = r.span("test.span")
    _start(tmp_path)
    try:
        with s:
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    (ev,) = _named(_host_events(tmp_path), "test.span")
    assert ev[3] - ev[2] >= 1.5e6              # the sleep, in ns
    # the registry is disabled: the trace holds it, the histogram does not
    assert s.snapshot()["count"] == 0
    assert _stack(s) == []


def test_span_nesting_and_threads_in_trace(tmp_path):
    r = Registry(enabled=True)
    outer, inner = r.span("test.outer"), r.span("test.inner")

    def worker():
        with inner:
            time.sleep(0.001)

    _start(tmp_path)
    try:
        with outer:
            with outer:                        # one handle nested in itself
                with inner:
                    time.sleep(0.001)
            t = threading.Thread(target=worker)
            t.start()
            t.join()
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(tmp_path)
    outers, inners = _named(ev, "test.outer"), _named(ev, "test.inner")
    assert len(outers) == 2 and len(inners) == 2
    big = max(outers, key=lambda e: e[3] - e[2])
    main_line = big[0]
    assert all(o[0] == main_line for o in outers)
    for e in outers + inners:                  # all inside the outermost
        assert big[2] <= e[2] and e[3] <= big[3]
    # the worker's span sits on a line (thread) of its own
    assert sorted(e[0] == main_line for e in inners) == [False, True]
    assert outer.snapshot()["count"] == 2 and inner.snapshot()["count"] == 2
    assert _stack(outer) == [] and _stack(inner) == []


def test_session_started_or_stopped_mid_span_keeps_stack_paired(tmp_path):
    r = Registry(enabled=False)
    s = r.span("test.mid")
    # started mid-span: the outer entry recorded nothing, the inner one
    # is annotated and pops its own entry
    with s:
        _start(tmp_path / "a")
        try:
            with s:
                assert len(_stack(s)) == 1
            assert _stack(s) == []
        finally:
            jax.profiler.stop_trace()
    assert _stack(s) == []
    assert len(_named(_host_events(tmp_path / "a"), "test.mid")) == 1
    # stopped mid-span: the inner entry (no session, nothing to record)
    # still pushes, so the outer's annotation is the one the outer closes
    _start(tmp_path / "b")
    with s:
        jax.profiler.stop_trace()
        with s:
            assert len(_stack(s)) == 2
        assert len(_stack(s)) == 1
    assert _stack(s) == []
    # the registry enabled mid-span: no start time, nothing observed
    with s:
        r.enable(True)
    assert s.snapshot()["count"] == 0 and _stack(s) == []


def test_span_closes_its_annotation_on_exception(tmp_path):
    r = Registry(enabled=True)
    s = r.span("test.raise")
    _start(tmp_path)
    try:
        with pytest.raises(ValueError):
            with s:
                raise ValueError("boom")
    finally:
        jax.profiler.stop_trace()
    assert _stack(s) == []
    assert s.snapshot()["count"] == 1
    assert len(_named(_host_events(tmp_path), "test.raise")) == 1


def test_disabled_registry_without_session_reads_no_clock(monkeypatch):
    r = Registry(enabled=False)
    s = r.span("test.quiet")
    with s:                                    # binds the profiler check
        pass

    def no_clock():
        raise AssertionError("a disabled span read the clock")
    monkeypatch.setattr(obs_registry.time, "perf_counter", no_clock)
    with s:
        with s:
            pass
    assert _stack(s) == []
    assert s.snapshot()["count"] == 0


# ---------------------------------------------------------------------------
# a profiled run and its programs
# ---------------------------------------------------------------------------
STEPS = 3
# item 3 of the loop/sampler spans: every one a fused-presample run enters
RUN_SPANS = ("loop.sync", "loop.dispatch", "loop.drain_feedback",
             "loop.emit.loop_start", "loop.emit.step_start",
             "loop.emit.step_timed", "loop.emit.step_end",
             "loop.emit.scores_ready", "loop.emit.loop_end",
             "sampler.pull_scores", "sampler.select", "engine.dispatch",
             "engine.take_rows")


def _fused_experiment():
    from repro.api import Experiment
    from repro.api.config import build_run
    ov = {"steps": STEPS, "imp.presample_impl": "fused",
          "imp.score_prune": "conservative", "imp.tau_th": 0.5,
          "max_step_retries": 0}
    return Experiment(build_run(arch="lm-tiny", preset="smoke",
                                overrides=ov))


def test_profiled_fit_carries_step_markers_and_spans(tmp_path):
    exp = _fused_experiment()
    assert not obs.enabled()                   # the bridge needs no registry
    _start(tmp_path)
    try:
        exp.fit()
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(tmp_path)
    train = _named(ev, "train")
    assert sorted(e[4]["step_num"] for e in train) == list(range(STEPS))
    loop_line = {e[0] for e in train}
    assert len(loop_line) == 1
    for name in RUN_SPANS:
        assert _named(ev, name), name
    # the loop's own spans run on the thread that carries the markers
    for name in ("loop.sync", "loop.emit.step_end", "sampler.select",
                 "sampler.pull_scores"):
        assert {e[0] for e in _named(ev, name)} == loop_line, name
    # one sync per step, inside its step
    syncs = sorted(_named(ev, "loop.sync"), key=lambda e: e[2])
    assert len(syncs) == STEPS
    for t, s in zip(sorted(train, key=lambda e: e[2]), syncs):
        assert t[2] <= s[2] and s[3] <= t[3]


def test_program_names_are_pinned():
    """The chip benchmark's readers find the update, the pool scoring and
    the row take by these module names in the device trace."""
    exp = _fused_experiment()
    state, _ = exp.init_state()
    T = exp.run.shape.seq_len
    b = exp.run.shape.global_batch
    B = b * exp.run.imp.presample_ratio
    pool = {"tokens": np.zeros((B, T), np.int32),
            "labels": np.zeros((B, T), np.int32)}
    batch = {"tokens": jnp.zeros((b, T), jnp.int32),
             "labels": jnp.zeros((b, T), jnp.int32),
             "weights": jnp.ones((b,), jnp.float32)}
    args = (state, batch) + ((jnp.float32(1.0),)
                             if exp.step_is_flagged else ())
    texts = {
        "jit_host_step": exp.step_fn.lower(*args).as_text(),
        "jit_pruned": exp.engine._fn_pruned(pool, b).lower(
            state["params"], pool, jnp.uint32(0)).as_text(),
        "jit__lambda": exp.engine._take.lower(
            pool, jnp.zeros((b,), jnp.int32)).as_text(),
    }
    for name, text in texts.items():
        assert text.startswith(f"module @{name} "), text[:120]
