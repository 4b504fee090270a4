"""``repro.obs`` — the process-local telemetry plane.

One global registry of typed instruments (counters, gauges,
exponential-bucket histograms, monotonic span timers) with cheap
module-level entry points used throughout the hot paths::

    from repro import obs

    obs.counter("plane.gather.calls").inc()
    obs.gauge("plane.queue_depth").set(q.qsize())
    with obs.span("plane.gather"):
        batch = assemble(plan)

Disabled (the default) every entry point reduces to a couple of
attribute checks — no clocks, no locks, no I/O — so the instrumentation
lives permanently in the loop, the data plane, the collectives, the
score store and the scoring engine. Measured on one TPU v5e with the
registry ENABLED (the ``prod`` preset): with no profiler session
xlstm-350m trains within -0.07 ... +0.18 % of the tokens/s it did
before the spans reached the profiler; with one, a traced step took
862.2 ms against 859.9 ms (IS cell) and 498.0 ms against 499.1 ms
(uniform cell), inside the runs' spread (PERF.md). Instruments are real
objects either way: a handle captured while disabled starts recording
the moment the registry is enabled.

While a JAX profiler session collects, every span is also a
``jax.profiler.TraceAnnotation`` of its name (``registry.Span``), so the
program's spans land in the trace on the device's clock.

Enablement is config-driven (``RunConfig.obs: ObsConfig``, dotted-CLI
addressable as ``--obs.enabled=true --obs.sink=jsonl ...``, on in the
``prod`` preset): ``Experiment`` calls ``obs.configure(run.obs)`` and
``Experiment.fit`` installs the ``VarianceGainHook`` (IS-health layer)
and ``TelemetryHook`` (sink flusher) automatically. See the README
"Observability" section for the instrument catalogue and the JSONL
record schema.
"""
from __future__ import annotations

from repro.obs.registry import Counter, Gauge, Histogram, Registry, Span

_registry = Registry(enabled=False)


def get_registry() -> Registry:
    return _registry


def enabled() -> bool:
    return _registry.enabled


def enable(on: bool = True) -> None:
    _registry.enable(on)


def configure(obs_cfg) -> None:
    """Apply an ``ObsConfig`` to the global registry (currently just the
    enable switch — sinks belong to the ``TelemetryHook`` so their
    lifetime is the run's, not the process's)."""
    _registry.enable(bool(obs_cfg.enabled))


def counter(name: str) -> Counter:
    return _registry.counter(name)


def gauge(name: str) -> Gauge:
    return _registry.gauge(name)


def histogram(name: str) -> Histogram:
    return _registry.histogram(name)


def span(name: str) -> Span:
    return _registry.span(name)


def snapshot() -> dict:
    return _registry.snapshot()


def reset() -> None:
    _registry.reset()


def __getattr__(name):
    # lazy: hook/health import repro.api (jax); keep `from repro import
    # obs` dependency-light for the modules that only record metrics
    if name in ("TelemetryHook",):
        from repro.obs.hook import TelemetryHook
        return TelemetryHook
    if name in ("VarianceGainHook", "ess", "variance_gain",
                "speedup_estimate"):
        from repro.obs import health
        return getattr(health, name)
    if name in ("Sink", "JsonlSink", "ConsoleSink", "TensorBoardSink",
                "make_sink"):
        from repro.obs import sinks
        return getattr(sinks, name)
    raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")


__all__ = ["Registry", "Counter", "Gauge", "Histogram", "Span",
           "get_registry", "enabled", "enable", "configure",
           "counter", "gauge", "histogram", "span", "snapshot", "reset",
           "TelemetryHook", "VarianceGainHook", "ess", "variance_gain",
           "speedup_estimate",
           "Sink", "JsonlSink", "ConsoleSink", "TensorBoardSink",
           "make_sink"]
