"""The trace-to-metrics reduction (``chipbench/tracefile.py``).

On hand-made events: the busy union, per-name sums and idle-gap
attribution, clipped to the window. On a small trace recorded on one
TPU v5e (``testdata/``): the reduction reads its device plane and
agrees with what the recording script measured.
"""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from chipbench import tracefile  # noqa: E402

MS = 1e6


def synthetic():
    # window 0..100 ms; ops overlap at 20..30; a gap 40..60 under "step"
    ops = [("fusion.1 fusion", 10 * MS, 30 * MS),
           ("dot.2 dot", 20 * MS, 40 * MS), ("dot.2 dot", 60 * MS, 90 * MS),
           ("late.3 copy", 95 * MS, 130 * MS),
           ("early.4 copy", -20 * MS, 5 * MS)]
    mods = [("jit_step", 10 * MS, 40 * MS), ("jit_step", 60 * MS, 90 * MS)]
    host = [("main", tracefile.WINDOW, 0.0, 100 * MS),
            ("main", "chipbench.step_dispatch", 35 * MS, 65 * MS),
            ("main", "chipbench.plane_finish", 45 * MS, 55 * MS)]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": mods}],
            "host": host, "window": (0.0, 100 * MS)}


def test_reduce_synthetic():
    r = tracefile.reduce(synthetic(), ("chipbench.step_dispatch",
                                       "chipbench.plane_finish"))
    assert r["window_s"] == pytest.approx(0.1)
    # busy: 0-5, 10-40, 60-90, 95-100 → 5 + 30 + 30 + 5 ms
    assert r["busy_s"] == pytest.approx(0.070)
    assert r["ops"]["dot.2 dot"] == pytest.approx(0.050)
    assert r["ops"]["late.3 copy"] == pytest.approx(0.005)
    assert r["modules"]["jit_step"] == pytest.approx(0.060)
    assert r["module_calls"]["jit_step"] == 2
    assert r["by_module"]["jit_step"] == pytest.approx(
        {"fusion": 0.020, "dot": 0.050})
    assert r["by_module"]["(none)"] == pytest.approx({"copy": 0.010})
    gaps = r["idle_gaps"]
    # 40-60 is under both annotations; the inner one (plane_finish) wins
    assert gaps["chipbench.plane_finish"] == pytest.approx(0.020)
    assert gaps["host: other"] == pytest.approx(0.010)   # 5-10, 90-95
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(0.1)


@pytest.mark.parametrize("busy", [0, 2])
def test_reduce_reads_only_the_cells_chips(busy):
    """Four device planes, one busy: reduced over the busy chip alone, as
    for a one-chip cell on a host of four, the trace reads that chip's
    own busy time, operations and idle gaps, not a quarter of them."""
    ann = ("chipbench.step_dispatch", "chipbench.plane_finish")
    one = synthetic()
    four = dict(one, devices=[
        {"name": f"/device:TPU:{i}", "ops": [], "modules": []}
        for i in range(4)])
    four["devices"][busy] = dict(one["devices"][0],
                                 name=f"/device:TPU:{busy}")
    mine = tracefile.reduce(four, ann, devices=[busy])
    assert mine.pop("planes") == [f"/device:TPU:{busy}"]
    alone = tracefile.reduce(one, ann)
    assert alone.pop("planes") == ["/device:TPU:0"]
    assert mine == alone
    assert mine["busy_s"] == pytest.approx(0.070)
    assert mine["ops"]["dot.2 dot"] == pytest.approx(0.050)
    assert mine["idle_gaps"]["chipbench.plane_finish"] == pytest.approx(0.020)
    every = tracefile.reduce(four, ann)
    assert every["busy_s"] == pytest.approx(0.070 / 4)
    assert tracefile.reduce(four, ann, devices=range(4)) == every


def test_op_name():
    assert tracefile.op_name(
        "%while.684 = (s32[]{:T(128)}, bf16[4,2048]{1,0:T(8,128)(2,1)}) "
        "while((s32[]{:T(128)}) %tuple.1), condition=%c") == "while.684 while"
    assert tracefile.op_name(
        "%fusion.2323 = f32[4,4096]{1,0:T(4,128)S(1)} fusion(f32[4,4096]"
        "{1,0:T(4,128)S(1)} %bitcast.3095), kind=kLoop") == \
        "fusion.2323 fusion"
    assert tracefile.op_name(
        "%custom-call.7 = (f32[12,1]{1,0}) custom-call(bf16[12,128,2048] "
        "%p), custom_call_target=\"tpu_custom_call\"") == \
        "custom-call.7 custom-call"


def test_merge():
    assert tracefile.merge([(0, 2), (1, 3), (5, 6), (5.5, 5.7)], 0, 10) == \
        [[0, 3], [5, 6]]
    assert tracefile.merge([(0, 2)], 1, 1.5) == [[1, 1.5]]


RECORDED = HERE.parent / "testdata" / "v5e_small.xplane.pb"
EXPECTED = HERE.parent / "testdata" / "v5e_small.json"


def test_reduce_recorded_chip_trace():
    """``testdata/record_trace.py`` ran two programs 5 and 3 times on the
    chip, each call timed by the host to its end."""
    want = json.loads(EXPECTED.read_text())
    r = tracefile.reduce(tracefile.load(RECORDED))
    assert r["module_calls"] == want["calls"]
    for name, host_s in want["host_s"].items():
        assert 0 < r["modules"][name] <= host_s
    assert 0 < r["busy_s"] <= sum(r["modules"].values()) * (1 + 1e-9)
    assert r["busy_s"] < r["window_s"]
    assert sum(r["idle_gaps"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)
    # and it reads today what it read when the trace was recorded
    assert r["busy_s"] == pytest.approx(want["reduced"]["busy_s"])
    assert r["ops"] == pytest.approx(want["reduced"]["ops"])
