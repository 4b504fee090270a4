"""Readings that the limits of ``correct`` are set from (not run by the
benchmark's own runs).

    python benchmarks/chip/calibrate.py dump --workload W --seeds 1,2,3
        per seed, everything the comparison reads: the program's checked
        steps (a run with a window of one step), the float32 reference's
        replay of them, the control (the reference with fp8 matrix
        products in the program's place) and a planted fault (half of
        each batch left out, the mean taken over the rest)
    python benchmarks/chip/calibrate.py faults --workload W --seeds 1,2 \\
        --faults half_batch,...
        per seed and fault, a run with the fault planted in the program
        (``chipbench/faults.py``) and a window of one step: every number
        the comparison reads, beside the cell's limits
    python benchmarks/chip/calibrate.py gradients --config C --traffic T \\
        --seeds 1,2 [--set key=value ...]
        three first-step gradients from one state on the same rows and
        weights: the program's, the reference's, and the program's with
        every bf16 weight moved one ulp (the yardstick of rounding)

Each prints one JSON line per seed. Needs a TPU; sizes are the cell's.
``compare.gaps`` turns a dump's sides into the numbers PERF.md tabulates.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

from run import REPO, setup_jax


def _seeds(s):
    return [int(x) for x in s.split(",") if x]


def _plain(x):
    import numpy as np
    if isinstance(x, dict):
        return {str(k): _plain(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_plain(v) for v in x]
    if isinstance(x, np.ndarray):
        return x.tolist()
    if isinstance(x, (np.floating, np.integer, np.bool_)):
        return x.item()
    return x


def dump(args):
    """Everything the comparison could read, per seed: the program's
    checked steps, the float32 reference, the fp8 control and the
    half-batch fault, for choosing which numbers separate; and each
    side's numbers against the reference (``gaps``). The embedding
    gradient's rows are left out of the line (they enter ``gaps``)."""
    from chipbench import compare, harness
    _, cfg, tr, limits = harness.load_cell(args.workload)
    for seed in _seeds(args.seeds):
        exp, rows = harness.build(cfg, tr, seed)
        hook, rec = harness.train(exp, cfg, seed, 0.0)
        prog = harness.program_readings(hook, rec)
        hook.loop = None
        del exp, rec
        gc.collect()
        out = {"seed": seed, "program": prog}
        out["f32"] = ref = harness.reference_readings(
            cfg, tr, seed, rows, prog["plans"], limits)
        out["fp8"] = harness.reference_readings(cfg, tr, seed, rows, None,
                                                limits, mode="fp8")
        out["half_batch"] = harness.reference_readings(
            cfg, tr, seed, rows, prog["plans"], limits, half_batch=True)
        out["gaps"] = {
            "program": compare.gaps(prog, ref, tr, seed),
            "fp8": compare.gaps(as_program(out["fp8"]), ref, tr, seed),
            "half_batch": compare.gaps(as_program(out["half_batch"]), ref,
                                       tr, seed)}
        for side in ("program", "f32", "fp8", "half_batch"):
            out[side].pop("embed_sq", None)
            out[side].pop("owner", None)
        print(json.dumps(_plain(out)), flush=True)


def faults(args):
    """Runs with a fault planted in the program, through the harness's
    own comparison."""
    from chipbench import faults as planted
    from chipbench import harness
    for seed in _seeds(args.seeds):
        for name in args.faults.split(","):
            readings = {}

            def log(m):
                if m.startswith("reading "):
                    k, v = m[len("reading "):].split(": ", 1)
                    readings[k] = v
            result, checks = harness.run(args.workload, seed, 0.0,
                                         fault=planted.BY_NAME[name],
                                         log=log)
            print(json.dumps({"seed": seed, "fault": name,
                              "correct": result["correct"],
                              "checks": result["checks"],
                              "readings": readings}), flush=True)
            gc.collect()


def as_program(ref):
    """A reference run's readings in the shape of the program's."""
    pools = {s: dict(p, alive=p["scores"] > -1) for s, p in
             ref["pools"].items()}
    plans = [(ref["pools"][s]["rows_used"], ref["pools"][s]["weights"])
             if s in ref["pools"] else (None, None)
             for s in range(len(ref["losses"]))]
    return dict(ref, pools=pools, plans=plans)


def gradients(args):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench import compare, harness, reference, traffic
    cfg = harness.load_json(REPO / "benchmarks/chip/configs" /
                            f"{args.config}.json")
    tr = traffic.load(args.traffic)
    for kv in args.set or ():
        k, v = kv.split("=", 1)
        v = json.loads(v)
        (tr if k in tr else cfg)[k] = v
    b = int(tr["global_batch"])
    for seed in _seeds(args.seeds):
        exp, rows = harness.build(cfg, tr, seed)
        p0 = reference.make_params(cfg, seed)
        ids = traffic.pool_ids(tr, 0)
        data = rows.gather(ids)
        if tr["scheme"] == "presample":
            _, sc = reference.pool_stats(p0, data["tokens"], data["labels"],
                                         cfg)
            kept = compare.race(sc, b, seed, 0)["idx"]
            w = compare.race_weights(sc, kept, seed, 0)
        else:
            kept, w = np.arange(b), np.ones((b,))
        batch = {"tokens": jnp.asarray(data["tokens"][kept]),
                 "labels": jnp.asarray(data["labels"][kept]),
                 "weights": jnp.asarray(np.asarray(w, np.float32))}
        flag = jnp.float32(1.0)

        def program_grad(params):
            from repro.core.is_train import train_state_init
            st = train_state_init(exp.lm, exp.opt, None, params=params)
            st, m = exp.step_fn(st, batch, flag)
            g = jax.tree_util.tree_map(lambda x: x / (1 - exp.run.optim.b1),
                                       st["opt"]["m"])
            return g, float(m["grad_norm"]), float(m["loss"])

        g_prog, gn_prog, l_prog = program_grad(p0)

        def ulp(key, x):
            if x.dtype != jnp.bfloat16:
                return x
            up = jax.random.bernoulli(key, 0.5, x.shape)
            return jnp.where(up, jnp.nextafter(x, jnp.inf),
                             jnp.nextafter(x, -jnp.inf)).astype(x.dtype)
        leaves, tree = jax.tree_util.tree_flatten(
            reference.make_params(cfg, seed))
        keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
        p_ulp = jax.tree_util.tree_unflatten(
            tree, [ulp(k, x) for k, x in zip(keys, leaves)])
        g_ulp, gn_ulp, l_ulp = program_grad(p_ulp)
        del p_ulp
        pf = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    reference.make_params(cfg, seed))
        l_ref, g_raw = reference.loss_and_grad(
            pf, data["tokens"][kept], data["labels"][kept], w, cfg)
        gn_ref = float(jnp.sqrt(sum(jnp.sum(x * x) for x in
                                    jax.tree_util.tree_leaves(g_raw))))
        clip = tr["optim"]["grad_clip"]
        s = min(1.0, clip / max(gn_ref, 1e-12))
        g_ref = jax.tree_util.tree_map(lambda x: x * s, g_raw)
        del g_raw, pf

        def elementwise(a, c):
            num = sum(float(jnp.sum(jnp.square(x - y))) for x, y in zip(
                jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(c)))
            den = sum(float(jnp.sum(jnp.square(y)))
                      for y in jax.tree_util.tree_leaves(c))
            return (num / den) ** 0.5

        n_prog = reference.leaf_norms(g_prog)
        n_ref = reference.leaf_norms(g_ref)
        n_ulp = reference.leaf_norms(g_ulp)
        out = {"seed": seed, "rows": [int(i) for i in kept],
               "loss": {"program": l_prog, "reference": l_ref,
                        "program_ulp": l_ulp},
               "grad_norm_before_clip": {"program": gn_prog,
                                         "reference": gn_ref,
                                         "program_ulp": gn_ulp},
               "leaf_norm_gap": {
                   "program_vs_reference": compare.leaf_gap(n_prog, n_ref),
                   "ulp_vs_program": compare.leaf_gap(n_ulp, n_prog)},
               "elementwise_gap": {
                   "program_vs_reference": elementwise(g_prog, g_ref),
                   "ulp_vs_program": elementwise(g_ulp, g_prog)}}
        print(json.dumps(out), flush=True)
        del exp, g_prog, g_ulp, g_ref
        gc.collect()


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = p.add_subparsers(dest="cmd", required=True)
    s = sub.add_parser("dump")
    s.add_argument("--workload", required=True)
    s.add_argument("--seeds", required=True)
    s = sub.add_parser("faults")
    s.add_argument("--workload", required=True)
    s.add_argument("--seeds", required=True)
    s.add_argument("--faults", required=True)
    s = sub.add_parser("gradients")
    s.add_argument("--config", required=True)
    s.add_argument("--traffic", required=True)
    s.add_argument("--seeds", required=True)
    s.add_argument("--set", action="append")
    args = p.parse_args(argv)
    jax = setup_jax()
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    {"dump": dump, "faults": faults, "gradients": gradients}[args.cmd](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
