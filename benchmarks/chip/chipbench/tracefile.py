"""Profiler trace → device metrics.

``Tracer`` records part of the window with JAX's profiler (Python tracing
off): the profiler starts a step before the traced part, so that the
device is being recorded when the part begins, and the part itself is
marked with a host annotation, ``WINDOW``. ``reduce`` reads the
``.xplane.pb`` it wrote with nothing but JAX:

* device planes are those named ``/device:TPU:<n>``; their line named
  ``XLA Ops`` holds one event per operation run on the chip, the line
  ``XLA Modules`` one event per execution of a compiled program;
* only the planes of the chips a cell ran on are read (``devices``, the
  ``<n>`` of their names; every plane where none are given), and each
  time below is a mean over them: an idle chip of the host that the cell
  does not use counts for nothing;
* busy time is the union of the operation intervals inside the window,
  averaged over the chips; the idle share is 1 − busy / window;
* program and operation times are the sums of their events' durations
  inside the window, keyed by name: a program's name is its jitted
  function's, ``jit_<name>``, with the trace's ``(id)`` suffix dropped;
  an operation's is its HLO name and opcode (``while.684 while``), and
  each operation is also charged, by opcode, to the program execution
  that encloses it (``by_module``);
* each idle gap is attributed to the innermost host annotation that
  covers its middle (the benchmark annotates its calls into the loop's
  layers, ``ANNOTATIONS``), else to ``host: other``.
"""
from __future__ import annotations

import re
import shutil
from pathlib import Path

import numpy as np

WINDOW = "chipbench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"\(\d+\)$")


class Tracer:
    def __init__(self, out_dir):
        self.dir = Path(out_dir)
        self.path = None
        self.closed = False
        self._ann = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)

    def open(self):
        import jax
        self._ann = jax.profiler.TraceAnnotation(WINDOW)
        self._ann.__enter__()

    def close(self):
        import jax
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.closed = True
        found = sorted(self.dir.rglob("*.xplane.pb"))
        self.path = found[-1] if found else None


_HLO = re.compile(r"^%?([\w.\-]+) = .*?(?<![\w\-])([a-z][a-z0-9\-_]*)\(")


def _name(n):
    return _SUFFIX.sub("", n)


def op_name(text):
    """``while.684 while`` from an HLO instruction's text; the text
    itself where it does not parse."""
    m = _HLO.match(text)
    return f"{m.group(1)} {m.group(2)}" if m else text[:80]


def load(path):
    """The trace as plain lists: {"devices": [{"ops": [(name, s, e)],
    "modules": [...]}], "host": [(line, name, s, e)], "window": (s, e)}
    with times in ns on the trace's clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, host, window = [], [], None
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            d = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                name = op_name if key == "ops" else _name
                d[key] = [(name(e.name), float(e.start_ns),
                           float(e.start_ns + e.duration_ns))
                          for e in line.events]
            devices.append(d)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    s, t = float(e.start_ns), float(e.start_ns + e.duration_ns)
                    if e.name == WINDOW:
                        window = (s, t)
                    host.append((line.name, e.name, s, t))
    return {"devices": devices, "host": host, "window": window}


def merge(intervals, lo, hi):
    """Union of (s, e) intervals clipped to [lo, hi], as sorted disjoint
    intervals."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _sum_by_name(events, lo, hi, count=False):
    out = {}
    for n, s, e in events:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            out[n] = out.get(n, 0.0) + (1 if count else d * 1e-9)
    return out


def _charge(ops, modules, lo, hi):
    """Seconds of each (program, opcode) in the window: every operation
    charged to the program execution whose interval holds its start."""
    mods = sorted((s, e, n) for n, s, e in modules)
    starts = [m[0] for m in mods]
    out = {}
    import bisect
    for name, s, e in ops:
        d = min(e, hi) - max(s, lo)
        if d <= 0:
            continue
        i = bisect.bisect_right(starts, s) - 1
        mod = mods[i][2] if i >= 0 and s < mods[i][1] else "(none)"
        key = (mod, name.rsplit(" ", 1)[-1])
        out[key] = out.get(key, 0.0) + d * 1e-9
    return out


def device_id(plane_name):
    return int(plane_name.rsplit(":", 1)[1])


def cell_planes(planes, devices=None):
    """The device planes of the chips ``devices`` (ids), or all."""
    if devices is None:
        return list(planes)
    devices = set(devices)
    return [p for p in planes if device_id(p["name"]) in devices]


def reduce(tr, annotations=(), devices=None):
    """Window, busy, idle gaps and per-name device times of a loaded
    trace, averaged over the planes of ``devices`` (ids; all planes where
    None). ``annotations``: host event names that may explain a gap."""
    if tr["window"] is None:
        raise ValueError("the trace holds no window annotation")
    lo, hi = tr["window"]
    window_s = (hi - lo) * 1e-9
    busy, ops, modules, calls, gaps = [], {}, {}, {}, {}
    by_module = {}
    ann = [(n, s, e) for _, n, s, e in tr["host"] if n in annotations]
    planes = cell_planes(tr["devices"], devices)
    for d in planes:
        merged = merge([(s, e) for _, s, e in d["ops"]], lo, hi)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for k, v in _sum_by_name(d["ops"], lo, hi).items():
            ops[k] = ops.get(k, 0.0) + v
        for k, v in _sum_by_name(d["modules"], lo, hi).items():
            modules[k] = modules.get(k, 0.0) + v
        for k, v in _sum_by_name(d["modules"], lo, hi, count=True).items():
            calls[k] = calls.get(k, 0) + v
        for (mod, opcode), v in _charge(d["ops"], d["modules"], lo,
                                        hi).items():
            by_module.setdefault(mod, {})
            by_module[mod][opcode] = by_module[mod].get(opcode, 0.0) + v
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            mid = 0.5 * (s + e)
            cover = [(ae - as_, n) for n, as_, ae in ann if as_ <= mid <= ae]
            what = min(cover)[1] if cover else "host: other"
            gaps[what] = gaps.get(what, 0.0) + (e - s) * 1e-9
    n = max(len(planes), 1)
    return {
        "planes": [d["name"] for d in planes],
        "window_s": window_s,
        "busy_s": float(np.mean(busy)) if busy else 0.0,
        "ops": {k: v / n for k, v in ops.items()},
        "modules": {k: v / n for k, v in modules.items()},
        "module_calls": {k: v / n for k, v in calls.items()},
        "by_module": {m: {k: v / n for k, v in d.items()}
                      for m, d in by_module.items()},
        "idle_gaps": {k: v / n for k, v in gaps.items()},
    }


def top(d, k=10):
    return [[n, v] for n, v in sorted(d.items(), key=lambda x: -x[1])[:k]]


def annotate(name, fn):
    """``fn`` wrapped in a host annotation named ``name``."""
    import jax

    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)
    return wrapped

