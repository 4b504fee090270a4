"""The toy kind of ``test_chip_kinds.py`` through the reference, unplaced
and laid out over a (data 2, model 2) mesh of four forced CPU devices;
prints one JSON line.

    PYTHONPATH=src:benchmarks/chip python benchmarks/chip/tests/kinds_mesh_check.py

It gives the largest gap between the two of the loss, of a gradient leaf
and of a pool score, each relative to the unplaced value's largest
magnitude, and for the placed weights and gradient the number of devices
each leaf lies on and whether each holds it whole. ``test_chip_kinds.py``
runs it in a subprocess: the forced device count has to be set before JAX
starts.
"""
import json
import os
import sys
from pathlib import Path

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                           + os.environ.get("XLA_FLAGS", ""))
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent)]

import jax  # noqa: E402
import numpy as np  # noqa: E402

import toy_kind  # noqa: E402
from chipbench import reference  # noqa: E402
from test_chip_kinds import CFG, SEED, data  # noqa: E402


def spread(tree):
    """{path: [devices the leaf lies on, whether each holds it whole]}."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): [len(x.sharding.device_set),
                                      x.sharding.is_fully_replicated]
            for p, x in flat}


def rel_gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def main():
    sys.modules[toy_kind.MODULE] = toy_kind
    place = reference.Placement(jax.devices()[:4], 2, 2)
    tokens, labels, weights = data()
    params = reference.make_params(CFG, SEED)
    placed = reference.make_params(
        CFG, SEED, place.tree(reference.param_shapes(CFG)))
    loss, grad = reference.loss_and_grad(params, tokens, labels, weights, CFG)
    loss_p, grad_p = reference.loss_and_grad(placed, tokens, labels, weights,
                                             CFG, place=place)
    _, sc = reference.pool_stats(params, tokens, labels, CFG)
    _, sc_p = reference.pool_stats(placed, tokens, labels, CFG, place=place)
    print(json.dumps({
        "devices": len(jax.devices()[:4]),
        "loss_rel_gap": rel_gap(loss_p, loss),
        "grad_rel_gap": max(rel_gap(a, b) for a, b in zip(
            jax.tree_util.tree_leaves(grad_p),
            jax.tree_util.tree_leaves(grad))),
        "score_rel_gap": rel_gap(sc_p, sc),
        "layout": {"params": spread(placed), "grad": spread(grad_p)}}))


if __name__ == "__main__":
    main()
