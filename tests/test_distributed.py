"""Multi-device distribution tests.

Each test runs tests/mp_checks.py in a subprocess with an 8-device forced
host platform (the main pytest process keeps 1 device so smoke tests and
benches see the normal environment).
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def _run(check):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    r = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "mp_checks.py"), check],
        env=env, capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"{check} failed:\n{r.stdout}\n{r.stderr[-3000:]}"
    assert f"OK {check.split('_')[0]}" in r.stdout or "OK" in r.stdout


@pytest.mark.parametrize("check", [
    "pipeline_parallel",
    "sharded_is_step_matches_single_device",
    "score_engine_sharded",
    "compressed_psum",
    "serve_sharded_equals_single",
    "slstm_kernel_sharded",
])
def test_multidevice(check):
    _run(check)


def test_two_process_jax_distributed_smoke():
    """True multi-process launch (ROADMAP item): 2 OS processes under
    jax.distributed drive every production collective — score gather,
    row all-gather/exchange, stats allreduce, candidate exchange — plus
    real sharded/gather plan chains, asserted digest-identical. CPU
    rides the coordination-service KV fallback; the same call sites ride
    multihost_utils on accelerator pods."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    r = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "mp_smoke.py"), "--launch"],
        env=env, capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr[-3000:]}"
    assert "2-process launch smoke OK" in r.stdout


def test_plan_determinism_across_two_processes():
    """The selection plane's acceptance check: TWO separate OS processes
    (disjoint 4-host subsets of an 8-host sharding, no shared memory)
    derive bitwise-identical presample plan chains, and both match the
    single-host run step-for-step."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    script = str(ROOT / "tests" / "plan_determinism_check.py")
    procs = [
        subprocess.run(
            [sys.executable, script, "--hosts", "8", "--host-set", hs,
             "--steps", "40"] + (["--single"] if i == 0 else []),
            env=env, capture_output=True, text=True, timeout=300)
        for i, hs in enumerate(["0,1,2,3", "4,5,6,7"])]
    digests = set()
    for r in procs:
        assert r.returncode == 0, r.stderr[-2000:]
        for line in r.stdout.strip().splitlines():
            digests.add(line.split()[-1])
    assert len(digests) == 1, f"plan chains diverged: {digests}"
