"""Run one cell of the on-chip benchmark once.

    python benchmarks/chip/run.py --workload xlstm-350m.is_on \\
        --seed 12345 --seconds 20 --trace 0

Prints, as the last line of standard output, one JSON object: ``correct``
(the comparison with the float32 reference, see ``chipbench/compare.py``),
``attempted`` / ``failed`` (training steps in the window), ``metrics``
(with ``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics from a profiler trace of the window) and ``device``.
The numbers compared for ``correct`` close standard error, each beside
its limit. Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.

Run from the root of a checkout; the program is imported from ``src/``.
JAX's compilation cache lives in ``benchmarks/chip/.cache/jax``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
REPO = HERE.parent.parent
CACHE = HERE / ".cache" / "jax"


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def setup_jax():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(REPO / "src"), str(HERE)]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def main(argv=None):
    args = parse(argv)
    jax = setup_jax()
    marks = {"jax_imported": time.perf_counter()}
    from chipbench import harness
    cell = harness.load_cell(args.workload)
    marks["harness_imported"] = time.perf_counter()
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"no accelerator: {e}", file=sys.stderr)
        return 2
    marks["devices"] = time.perf_counter()
    if devices[0].platform != "tpu" or len(devices) < cell[0]["chips"]:
        print(f"this cell needs {cell[0]['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    result, checks = harness.run(
        args.workload, args.seed, args.seconds, trace=bool(args.trace),
        cell=cell, t_start=T_START, marks=marks,
        log=lambda m: print(m, file=sys.stderr, flush=True))
    print(f"correct: {result['correct']}", file=sys.stderr)
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r}) "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
