"""A whole run of each cell at a small size on the CPU, without the
harness's look for a chip: clean, it comes out ``correct``; with the timed
path broken underneath, it does not. Also the control (the reference
with fp8 matrix products in the program's place) against the cell's
limits, and the copied selection hash against the program's.

The small model keeps each cell's layer pattern at narrow widths and
float32 weights, so that the clean program sits far inside the limits
set for the bfloat16 cell on the chip, and any failure is the fault's.
"""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from chipbench import compare, faults, harness, traffic  # noqa: E402

CELLS = {"xlstm-350m.is_on": ("xlstm-350m", "is_on"),
         "xlstm-350m.uniform": ("xlstm-350m", "uniform")}
SEED = 2 ** 31 + 77


def small_cell(workload, repeats=1):
    conf, tr_name = CELLS[workload]
    cfg = json.loads((HERE.parent / "configs" / f"{conf}.json").read_text())
    cfg.update(d_model=32, n_heads=2, n_kv_heads=2, vocab_size=64,
               dtype="float32")
    cfg["ssm"] = dict(cfg["ssm"], chunk=8)
    for seg in cfg["segments"]:
        seg["repeats"] = repeats
    tr = dict(traffic.load(tr_name), seq_len=32, global_batch=2,
              probe_rows=2, states=16)
    limits = json.loads((HERE.parent / "limits" /
                         f"{workload}.json").read_text())
    return {"name": workload, "chips": 1}, cfg, tr, limits


FAULTS = {w: faults.FAULTS[traffic.load(t)["scheme"]]
          for w, (_, t) in CELLS.items()}
RUNS = [(w, None) for w in CELLS] + [(w, f) for w in CELLS
                                      for f in FAULTS[w]]


@pytest.fixture(autouse=True)
def score_in_float32(monkeypatch):
    """The small model scores its pools in float32 too; the process-wide
    telemetry registry, which the prod preset switches on, is left as it
    was found."""
    from repro import obs
    from repro.api.config import apply_overrides
    orig = harness.run_config
    monkeypatch.setattr(harness, "run_config", lambda *a: apply_overrides(
        orig(*a), {"imp.score_dtype": "float32"}))
    was = obs.enabled()
    yield
    obs.reset()
    obs.enable(was)


@pytest.mark.parametrize("workload,fault", RUNS,
                         ids=[f"{w}-{f.__name__ if f else 'clean'}"
                              for w, f in RUNS])
def test_run_correct_only_when_unbroken(workload, fault):
    result, checks = harness.run(workload, SEED, 0.2,
                                 cell=small_cell(workload), fault=fault,
                                 log=lambda m: None)
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"tokens_per_s", "probe_loss",
                                      "setup_s"}
    assert list(result)[-1] == "checks"
    assert result["correct"] is (fault is None), checks


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_control_fails_the_limits(workload):
    """Two periods: the rounding of the fp8 products compounds through the
    recurrent layers as it does through the cell's 24."""
    from calibrate import as_program
    _, cfg, tr, limits = small_cell(workload, repeats=2)
    rows = traffic.Rows(tr, cfg["vocab_size"], SEED)
    ref = harness.reference_readings(cfg, tr, SEED, rows, None, limits)
    fp8 = harness.reference_readings(cfg, tr, SEED, rows, None, limits,
                                     mode="fp8")
    g = compare.gaps(as_program(fp8), ref, tr, SEED)
    over = [k for k, v in limits.items() if k in g and g[k] > v]
    assert over, g


def test_selection_hash_matches_program():
    from repro.sampler import selection
    rng = np.random.default_rng(0)
    for step in range(5):
        s = rng.uniform(0.5, 2.0, size=12)
        seed = 2 ** 31 + 999 * step
        idx, _, w, thr, _ = selection.presample_race_select_raw(
            s, 4, ctx=selection.hash_context(seed, compare.SALT, step))
        mine = compare.race(s, 4, seed, step)["idx"]
        np.testing.assert_array_equal(mine, idx)
        np.testing.assert_allclose(compare.race_weights(s, idx, seed, step),
                                   w, rtol=1e-6)
        assert compare.select_gap(s, idx, seed, step) == 0.0
        assert compare.select_gap(s, mine[::-1][:3].tolist()
                                  + [int(np.argmax(compare.keys(
                                      s, seed, step)))], seed, step) > 0


def test_row_grads_read_each_rows_own_tokens():
    """A row's reading is the norm over the tokens it alone holds: a row
    left out reads 0 there, one weighted double reads twice its norm."""
    owner = compare.owner_masks([[0, 1, 2], [2, 3], [4, 5, 5]], 8)
    np.testing.assert_array_equal(owner, [1, 1, 3, 2, 4, 4, 0, 0])
    sq = np.array([1.0, 3.0, 7.0, 4.0, 9.0, 16.0, 2.0, 2.0])
    assert compare.row_grads(sq, owner, 3) == [2.0, 2.0, 5.0]
    ref = {"owner": owner, "embed_sq": sq, "row_scores": [np.ones(3)]}
    assert compare.row_grad_gap(dict(ref), ref) == 0.0
    dropped = sq * np.where(owner == 4, 0.0, 1.0)
    assert compare.row_grad_gap({"embed_sq": dropped}, ref) == 1.0
    doubled = sq * np.where(owner == 1, 4.0, 1.0)
    assert compare.row_grad_gap({"embed_sq": doubled}, ref) == 1.0
    assert compare.row_grads(sq, compare.owner_masks([[0], [0]], 8),
                             2) == [None, None]
