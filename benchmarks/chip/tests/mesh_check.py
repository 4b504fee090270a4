"""Runs of a small cell on a forced 8-device CPU host; prints one JSON line.

    PYTHONPATH=src:benchmarks/chip python benchmarks/chip/tests/mesh_check.py \\
        xlstm-350m.uniform one four four_half_batch

``one`` runs the cell on one chip; ``four`` on four, as a (data 2,
model 2) mesh written into its configuration; ``four_half_batch`` the
same with the ``half_batch`` fault planted. For each: ``correct``, the
numbers compared, and the readings the reference is compared with; for
``four``, the number of devices each leaf of the program's train state
(as built, and after the first step) and of the reference's params, m and
v lies on, and whether it is whole on each. ``tests/test_chip_mesh.py``
runs it in subprocesses: the forced device count has to be set before
JAX starts.
"""
import json
import os
import sys
from pathlib import Path

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent)]

import jax  # noqa: E402

from chipbench import faults, harness, reference  # noqa: E402
from test_chip_harness import SEED, small_cell  # noqa: E402

MESH = {"data": 2, "model": 2}


def spread(tree):
    """{path: [devices the leaf lies on, whether each holds it whole]}."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): [len(x.sharding.device_set),
                                      x.sharding.is_fully_replicated]
            for p, x in flat}


def main(workload, runs):
    from repro.api.config import apply_overrides
    run_config = harness.run_config
    # as test_chip_harness.py: the small float32 model scores in float32
    harness.run_config = lambda *a: apply_overrides(
        run_config(*a), {"imp.score_dtype": "float32"})
    readings, layout = [], {}
    program_readings = harness.program_readings

    def keep_readings(hook, rec):
        readings.append(program_readings(hook, rec))
        return readings[-1]
    harness.program_readings = keep_readings
    adamw_step = reference.adamw_step

    def adamw_laid_out(*a, **k):
        out = adamw_step(*a, **k)
        if k.get("place") is not None:
            layout.setdefault("reference", spread(out[:3]))
        return out
    reference.adamw_step = adamw_laid_out

    def observe(exp, seed):
        init, step = exp.init_state, exp.step_fn

        def init_state():
            state, pstate = init()
            layout["program_init"] = spread(state)
            return state, pstate

        def step_fn(state, *a):
            out = step(state, *a)
            layout.setdefault("program_step", spread(out[0]))
            return out
        exp.init_state, exp.step_fn = init_state, step_fn

    cell_e, cfg, tr, limits = small_cell(workload)
    four = (dict(cell_e, chips=4), dict(cfg, mesh=MESH), tr, limits)
    cells = {"one": ((cell_e, cfg, tr, limits), None),
             "four": (four, observe),
             "four_half_batch": (four, faults.half_batch)}
    out = {"limits": limits}
    for name in runs:
        cell, fault = cells[name]
        result, checks = harness.run(workload, SEED, 0.2, cell=cell,
                                     fault=fault, log=lambda m: None)
        out[name] = {"correct": result["correct"],
                     "device": result["device"],
                     "checks": {c["name"]: c["value"] for c in checks},
                     "readings": {k: readings[-1][k] for k in
                                  ("losses", "grad_norms", "update_norms")}}
    out["layout"] = layout
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2:])
