"""A cell of four chips, run whole on a forced 8-device CPU host
(``mesh_check.py``, in subprocesses: the device count is fixed before JAX
starts, and this process keeps its one device).

The small cell of ``test_chip_harness.py`` at ``chips`` 4 with a (data 2,
model 2) mesh in its configuration: the program is built on that mesh,
its weights made on its own shardings, and the float32 reference laid out
over the same four devices. Clean, it comes out ``correct``, and reads
what the same cell reads on one chip; with half of each batch left out it
does not; and no leaf of the program's train state or of the reference's
params, m and v lies on one device alone.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[2]
sys.path.insert(0, str(HERE.parent))

from chipbench import compare  # noqa: E402

WORKLOADS = ["xlstm-350m.uniform", "xlstm-350m.is_on"]
# Float32 weights in the small cell: a sum split over chips changes the
# order of its terms and so moves a loss by float32 rounding (a few 1e-8,
# relative, on this cell); a row or a shard left out moves it by percent.
LOSS_RTOL = 1e-5


def _launch(workload, *runs):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [str(REPO / "src"), str(HERE.parent)]))
    return subprocess.Popen(
        [sys.executable, str(HERE / "mesh_check.py"), workload, *runs],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


@pytest.fixture(scope="module", params=WORKLOADS)
def runs(request):
    """The one-chip run and the four-chip runs of a cell, side by side."""
    procs = [_launch(request.param, "one"),
             _launch(request.param, "four", "four_half_batch")]
    out = {}
    for p in procs:
        stdout, stderr = p.communicate(timeout=600)
        assert p.returncode == 0, stderr[-4000:]
        out.update(json.loads(stdout.strip().splitlines()[-1]))
    return out


def test_four_chip_run_is_correct(runs):
    assert runs["four"]["correct"] is True, runs["four"]["checks"]
    assert runs["one"]["correct"] is True, runs["one"]["checks"]
    assert runs["four"]["device"]["count"] == 8


def test_four_chip_run_reads_as_one_chip(runs):
    one, four = runs["one"]["readings"], runs["four"]["readings"]
    limits = runs["limits"]
    for a, b in zip(four["losses"], one["losses"]):
        assert abs(a - b) <= LOSS_RTOL * abs(b), (four["losses"],
                                                   one["losses"])
    gap, leaf = compare.leaf_gap(four["grad_norms"], one["grad_norms"])
    assert gap <= limits["grad_gap"], (gap, leaf)
    gap, leaf = compare.leaf_gap(four["update_norms"], one["update_norms"])
    assert gap <= limits["update_gap"], (gap, leaf)


def test_four_chip_half_batch_fails(runs):
    r = runs["four_half_batch"]
    assert r["correct"] is False
    over = [k for k, v in r["checks"].items() if v > runs["limits"][k]]
    assert over, r["checks"]


@pytest.mark.parametrize("part", ["program_init", "program_step",
                                  "reference"])
def test_four_chip_leaves_span_the_chips(runs, part):
    """Every leaf lies on all four devices; the embedding, the largest,
    is split (no device holds it whole)."""
    layout = runs["layout"][part]
    assert layout and all(n == 4 for n, _ in layout.values()), layout
    embed = [whole for path, (_, whole) in layout.items()
             if "'embed'" in path]
    assert embed and not any(embed), layout
