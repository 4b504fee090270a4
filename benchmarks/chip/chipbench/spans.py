"""The program's own spans on the device trace's clock.

While a profiler session collects, each ``repro.obs`` span of the program
is also a host annotation of its name (``obs.Span``), recorded on the
thread that ran it, and the loop marks each step with a ``train`` step
annotation (``TrainLoop``). This module reads them from a traced run's
``.xplane.pb``:

* program spans are the host events whose name the program's schema
  declares as a span (``repro.obs.schema``, kind ``span``; ``*`` stands
  for a family); the ``train`` marker is not one;
* the loop's thread is the host line that carries the ``train`` markers;
* each device idle gap inside the window is split at the spans' edges,
  and each part is charged to the innermost program span that covers it:
  a span on the loop's thread before one on a worker thread, then the
  shortest. What no span covers is ``UNATTRIBUTED``;
* a span's host time is the union of its events on each thread, clipped
  to the window.

Times are per traced step (``ctx.traced_steps``), device idle averaged
over the chips. Host lines are told apart by their position in the
trace: every Python thread carries the same name there, which is all
``tracefile.load`` keeps of a line.

The whole table of a trace, for a person::

    PYTHONPATH=src:benchmarks/chip python -m chipbench.spans <xplane.pb>
"""
from __future__ import annotations

import re

from chipbench import tracefile

STEP = "train"
UNATTRIBUTED = "unattributed"


def _schema_spans():
    from repro.obs import schema
    return [name for name, kind, _ in schema.entries() if kind == "span"]


def span_matcher(patterns=None):
    """A predicate: is this host event name a program span, one of
    ``patterns`` (schema-style names, ``*`` for a family; by default
    every span of the program's schema)?"""
    patterns = _schema_spans() if patterns is None else list(patterns)
    exact = {p for p in patterns if "*" not in p}
    rx = [re.compile("".join(".+" if c == "*" else re.escape(c) for c in p))
          for p in patterns if "*" in p]
    return lambda name: name in exact or any(r.fullmatch(name) for r in rx)


def load(path, devices=None):
    """A trace as plain lists, times in ns: {"window": (s, e), "devices":
    [[(s, e) of each operation]] of the chips ``devices`` (ids; every
    chip where None), "lines": [[(name, s, e)] of each host thread:
    program spans and step markers only]}."""
    from jax.profiler import ProfileData
    is_span = span_matcher()
    devices = None if devices is None else set(devices)
    out, lines, window = [], [], None
    for plane in ProfileData.from_file(str(path)).planes:
        if tracefile.DEVICE_PLANE.match(plane.name):
            if devices is not None \
                    and tracefile.device_id(plane.name) not in devices:
                continue
            for line in plane.lines:
                if line.name == tracefile.OPS_LINE:
                    out.append([(float(e.start_ns), float(e.end_ns))
                                for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                kept = []
                for e in line.events:
                    if e.name == tracefile.WINDOW:
                        window = (float(e.start_ns), float(e.end_ns))
                    elif e.name == STEP or is_span(e.name):
                        kept.append((e.name, float(e.start_ns),
                                     float(e.end_ns)))
                lines.append(kept)
    return {"window": window, "devices": out, "lines": lines}


def _owners(tr):
    """The window cut at every span edge: [(s, e, owner)], the owner the
    innermost covering span, loop thread first."""
    is_span = span_matcher()
    lo, hi = tr["window"]
    loop = {i for i, line in enumerate(tr["lines"])
            if any(n == STEP for n, _, _ in line)}
    spans = [(s, e, i not in loop, n) for i, line in enumerate(tr["lines"])
             for n, s, e in line if n != STEP and is_span(n)
             and s < hi and e > lo]
    cuts = sorted({lo, hi} | {x for s, e, _, _ in spans for x in (s, e)
                              if lo < x < hi})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        mid = 0.5 * (a + b)
        cover = [(worker, e - s, n) for s, e, worker, n in spans
                 if s <= mid < e]
        out.append((a, b, min(cover)[2] if cover else UNATTRIBUTED))
    return out


def idle_by_span(tr):
    """Seconds of device idle inside the window charged to each owner
    (a span's name or ``UNATTRIBUTED``), averaged over the chips."""
    lo, hi = tr["window"]
    owners = _owners(tr)
    out = {}
    for ops in tr["devices"]:
        busy = tracefile.merge(ops, lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        j = 0
        for s, e in gaps:
            while j < len(owners) and owners[j][1] <= s:
                j += 1
            k = j
            while k < len(owners) and owners[k][0] < e:
                a, b, who = owners[k]
                d = min(b, e) - max(a, s)
                if d > 0:
                    out[who] = out.get(who, 0.0) + d * 1e-9
                k += 1
    n = max(len(tr["devices"]), 1)
    return {k: v / n for k, v in out.items()}


def host_s(tr, match):
    """Host seconds inside the window under the spans ``match`` accepts:
    the union of their events on each thread, summed over threads."""
    lo, hi = tr["window"]
    total = 0.0
    for line in tr["lines"]:
        ivs = [(s, e) for n, s, e in line if n != STEP and match(n)]
        total += sum(e - s for s, e in tracefile.merge(ivs, lo, hi))
    return total * 1e-9


def found(tr, match):
    return any(n != STEP and match(n) for line in tr["lines"]
               for n, _, _ in line)


def traced(ctx):
    """The traced run's spans, loaded once per run (cached on ``ctx``);
    None where the trace holds no program span, as a program without the
    profiler bridge writes."""
    if not hasattr(ctx, "spans_trace"):
        tracer = getattr(ctx.hook, "tracer", None)
        path = getattr(tracer, "path", None)
        tr = load(path, getattr(ctx, "devices", None)) \
            if path is not None else None
        if tr is not None and (tr["window"] is None or not found(
                tr, span_matcher())):
            tr = None
        ctx.spans_trace = tr
    return ctx.spans_trace


def table(ctx):
    """Per traced step: ``idle_ms`` by owner, ``host_ms`` by span name,
    and ``idle_total_ms``; None where there is nothing to read."""
    if not hasattr(ctx, "spans_table"):
        tr = traced(ctx)
        n = ctx.traced_steps
        ctx.spans_table = None if tr is None or not n else summarize(tr, n)
    return ctx.spans_table


def summarize(tr, steps):
    is_span = span_matcher()
    idle = idle_by_span(tr)
    names = sorted({n for line in tr["lines"] for n, _, _ in line
                    if n != STEP and is_span(n)})
    return {"steps": steps,
            "idle_total_ms": 1e3 * sum(idle.values()) / steps,
            "idle_ms": {k: 1e3 * v / steps for k, v in idle.items()},
            "host_ms": {k: 1e3 * host_s(tr, lambda x, k=k: x == k) / steps
                        for k in names}}


def idle_ms(ctx, name):
    """Device-idle ms per traced step charged to span ``name``; None where
    the trace has no such span."""
    t = table(ctx)
    if t is None or not found(ctx.spans_trace, lambda n: n == name):
        return None
    return t["idle_ms"].get(name, 0.0)


def host_ms(ctx, pattern):
    """Host ms per traced step under the spans ``pattern`` names (a
    schema-style name, ``*`` for a family), clipped to the window; None
    where the trace has no such span."""
    t = table(ctx)
    match = span_matcher([pattern])
    if t is None or not found(ctx.spans_trace, match):
        return None
    return 1e3 * host_s(ctx.spans_trace, match) / t["steps"]


def _steps_in_window(tr):
    lo, hi = tr["window"]
    return sum(1 for line in tr["lines"] for n, s, _ in line
               if n == STEP and lo <= s < hi)


def main(argv=None):
    import argparse
    import json
    p = argparse.ArgumentParser(description="per-span idle table of a "
                                "traced run")
    p.add_argument("path")
    tr = load(p.parse_args(argv).path)
    if tr["window"] is None or not found(tr, span_matcher()):
        raise SystemExit("the trace holds no window or no program span")
    # the traced steps: the step markers that start inside the window
    print(json.dumps(summarize(tr, max(_steps_in_window(tr), 1)),
                     indent=1, sort_keys=True))


if __name__ == "__main__":
    main()
