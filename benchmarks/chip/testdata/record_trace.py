"""Record the small trace the reduction is tested on (one TPU v5e).

    python benchmarks/chip/testdata/record_trace.py

Runs two jitted programs a known number of times inside a traced window
(after one call that lets the profiler warm up), each call timed by the
host clock to its end, and writes the trace
(``v5e_small.xplane.pb``) and what was done (``v5e_small.json``: calls
per program, host seconds per program).
"""
import json
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import tracefile  # noqa: E402


def main():
    assert jax.devices()[0].platform == "tpu"
    # keep the machine's directories out of the trace's source locations
    jax.config.update("jax_hlo_source_file_canonicalization_regex", ".*/")

    @jax.jit
    def small_matmul(a, b):
        return jnp.tanh(a @ b) @ b

    @jax.jit
    def small_reduce(a):
        return jnp.sum(jnp.exp(a), axis=0)

    a = jnp.ones((1024, 1024), jnp.bfloat16)
    small_matmul(a, a).block_until_ready()
    small_reduce(a).block_until_ready()
    out = HERE / "_trace"
    tracer = tracefile.Tracer(out)
    calls = {"jit_small_matmul": 5, "jit_small_reduce": 3}
    host = {k: 0.0 for k in calls}
    tracer.start()
    small_reduce(a).block_until_ready()     # the profiler warms up
    tracer.open()
    # the profiler stamps device events up to ~1 ms off the host clock
    # (measured on v5e); keep the calls clear of the window's edges
    time.sleep(0.005)
    for _ in range(calls["jit_small_matmul"]):
        t = time.perf_counter()
        small_matmul(a, a).block_until_ready()
        host["jit_small_matmul"] += time.perf_counter() - t
        time.sleep(0.002)
    for _ in range(calls["jit_small_reduce"]):
        t = time.perf_counter()
        small_reduce(a).block_until_ready()
        host["jit_small_reduce"] += time.perf_counter() - t
    time.sleep(0.005)
    tracer.close()
    shutil.copy(tracer.path, HERE / "v5e_small.xplane.pb")
    shutil.rmtree(out)
    r = tracefile.reduce(tracefile.load(HERE / "v5e_small.xplane.pb"))
    (HERE / "v5e_small.json").write_text(json.dumps(
        {"device_kind": jax.devices()[0].device_kind, "calls": calls,
         "host_s": host, "reduced": r}, indent=1, sort_keys=True))
    print(json.dumps(r, sort_keys=True))


if __name__ == "__main__":
    main()
