import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: AOT lower + compile every (architecture × input shape)
cell on the production mesh, and extract the roofline terms from the
compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k \
        --mesh pod --variant is_chunked

Results are persisted incrementally to benchmarks/artifacts/dryrun/*.json
(existing cells are skipped unless --force), so the sweep is resumable.

Roofline terms (TPU v5e):
    compute    = HLO_FLOPs_per_chip / 197e12
    memory     = HLO_bytes_per_chip / 819e9
    collective = collective_bytes_per_chip / 50e9   (ICI, per link)
"""
import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

PEAK_FLOPS = 197e12      # bf16 / chip
HBM_BW = 819e9           # bytes/s / chip
ICI_BW = 50e9            # bytes/s / link

ART_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "artifacts" / "dryrun"

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"^\s*(?:%\S+\s*=\s*)?(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.M)
_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|"
                       r"s16|u16|s8|u8|pred|c64|c128)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str):
    """Sum result-shape bytes of every collective op (skip *-done: the
    matching *-start already carries the shape)."""
    per_kind = {}
    for m in _COLL_RE.finditer(hlo_text):
        shapes, kind = m.group(1), m.group(2)
        b = _shape_bytes(shapes)
        per_kind[kind] = per_kind.get(kind, 0) + b
    return per_kind, sum(per_kind.values())


# ---------------------------------------------------------------------------
def build_cell(arch: str, shape_name: str, mesh_kind: str, variant: str):
    """Returns (mesh, jitted_fn, example_args tuple of ShapeDtypeStructs,
    meta, score_bundle). ``score_bundle`` is (score_fn, score_args) for
    IS train variants — the decoupled engine's forward-only score fn,
    lowered and costed SEPARATELY from the update fn — else None."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.base import SHAPES, ISConfig, OptimConfig, RunConfig
    from repro.core.is_train import build_train_step, build_uniform_step, train_state_init
    from repro.distributed import sharding as shd
    from repro.launch.mesh import (choose_microbatches, data_parallel_size,
                                   make_production_mesh)
    from repro.launch.specs import serve_input_specs, train_input_specs
    from repro.models.lm import LM
    from repro.optim.api import get_optimizer
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    dp = data_parallel_size(mesh)
    lm = LM(cfg)
    named = lambda tree: shd.to_named(tree, mesh)

    if shape.kind == "train":
        micro = choose_microbatches(cfg, dp, shape.global_batch)
        ratio = 3 if variant.startswith("is") else 1
        impl_map = {"is_naive": "naive", "is_chunked": "chunked"}
        icfg = ISConfig(enabled=variant.startswith("is"), presample_ratio=3,
                        score_impl=impl_map.get(variant, "fused"))
        run = RunConfig(model=cfg, shape=shape, imp=icfg,
                        optim=OptimConfig(name="sgd"), microbatches=micro)
        opt = get_optimizer(run.optim)
        batch_sds = train_input_specs(cfg, shape, presample_ratio=ratio)
        key = jax.random.PRNGKey(0)
        state_sds = jax.eval_shape(lambda k: train_state_init(lm, opt, k), key)
        state_specs = shd.state_specs(cfg, state_sds, mesh, zero1=True)
        batch_specs = shd.batch_specs(cfg, batch_sds, mesh)
        if variant == "uniform":
            step = build_uniform_step(lm, run, opt)
        else:
            step = build_train_step(lm, run, opt, gate="always")
        fn = jax.jit(step,
                     in_shardings=(named(state_specs), named(batch_specs)),
                     out_shardings=(named(state_specs), None),
                     donate_argnums=(0,))
        meta = {"microbatches": micro, "presample_ratio": ratio,
                "step": "train_step"}
        score_bundle = None
        if variant.startswith("is"):
            # the decoupled scoring engine's fn: forward-only, score_dtype,
            # no remat, batch sharded over dp, params on their train layout
            from repro.scoring import ScoreEngine
            engine = ScoreEngine(lm, run)
            pspecs = shd.param_specs(cfg, state_sds["params"], mesh)
            score_fn = jax.jit(engine.fwd,
                               in_shardings=(named(pspecs),
                                             named(batch_specs)))
            score_bundle = (score_fn, (state_sds["params"], batch_sds))
        return mesh, fn, (state_sds, batch_sds), meta, score_bundle

    # serving
    batch_sds, cache_sds = serve_input_specs(cfg, shape)
    params_sds = lm.init_shapes(jax.random.PRNGKey(0))
    pspecs = shd.param_specs(cfg, params_sds, mesh)
    cspecs = shd.cache_specs(cfg, cache_sds, mesh)
    bspecs = shd.batch_specs(cfg, batch_sds, mesh)

    def serve(params, caches, batch):
        return lm.serve_step(params, caches, batch)

    fn = jax.jit(serve,
                 in_shardings=(named(pspecs), named(cspecs), named(bspecs)),
                 out_shardings=(None, named(cspecs)),
                 donate_argnums=(1,))
    meta = {"step": "serve_step", "kind": shape.kind}
    return mesh, fn, (params_sds, cache_sds, batch_sds), meta, None


def run_cell(arch: str, shape_name: str, mesh_kind: str, variant: str,
             out_dir: Path = ART_DIR, force=False):
    from repro.configs import get_config
    from repro.configs.base import SHAPES

    out_dir.mkdir(parents=True, exist_ok=True)
    cell_id = f"{arch}__{shape_name}__{mesh_kind}__{variant}"
    out_path = out_dir / f"{cell_id}.json"
    if out_path.exists() and not force:
        prev = json.loads(out_path.read_text())
        if prev.get("ok"):        # failed cells are always retried
            print(f"[skip] {cell_id}")
            return prev

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "variant": variant, "ok": False}
    t0 = time.time()
    try:
        mesh, fn, args, meta, score_bundle = build_cell(
            arch, shape_name, mesh_kind, variant)
        rec.update(meta)
        n_chips = mesh.devices.size
        with jax.set_mesh(mesh):
            lowered = fn.lower(*args)
            t_lower = time.time()
            compiled = lowered.compile()
            t_compile = time.time()
            score_compiled = None
            if score_bundle is not None:
                score_fn, score_args = score_bundle
                score_compiled = score_fn.lower(*score_args).compile()
            t_score = time.time()

        # trip-count-aware analysis (XLA's cost_analysis counts scan
        # bodies once — see repro.launch.hlo_cost and tests/test_hlo_cost).
        # The engine's score fn is costed SEPARATELY from the update fn —
        # its per-chip cost is the B term of the paper's speedup criterion.
        from repro.launch.hlo_cost import analyze_fns
        hlos = {"update_fn": compiled.as_text()}
        if score_compiled is not None:
            hlos["score_fn"] = score_compiled.as_text()
        costs = analyze_fns(hlos)
        hc = costs["update_fn"]
        flops = hc["flops"]
        bytes_accessed = hc["bytes"]
        if "score_fn" in costs:
            sc = costs["score_fn"]
            s_terms = {"compute_s": sc["flops"] / PEAK_FLOPS,
                       "memory_s": sc["bytes"] / HBM_BW,
                       "collective_s": sc["collective_bytes"] / ICI_BW}
            rec["score_fn"] = {
                "flops_per_chip": sc["flops"],
                "bytes_per_chip": sc["bytes"],
                "collective_bytes_per_chip": sc["collective_bytes"],
                "collectives": sc["collectives"],
                "terms": s_terms,
                "dominant": max(s_terms, key=s_terms.get),
                "compile_s": round(t_score - t_compile, 2),
                # score cost relative to the update step (should sit near
                # the paper's B/(B+3b) forward-equivalents fraction)
                "flops_frac_of_update": (sc["flops"] / flops) if flops else None,
            }
        ca = compiled.cost_analysis() or {}
        if isinstance(ca, (list, tuple)):   # older jaxlib: entry per device
            ca = ca[0] if ca else {}
        rec["xla_flops_uncorrected"] = float(ca.get("flops", 0.0))
        try:
            ma = compiled.memory_analysis()
            mem = {
                "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
                "output_bytes": getattr(ma, "output_size_in_bytes", None),
                "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
                "peak_bytes": getattr(ma, "peak_memory_in_bytes", None),
            }
        except Exception as e:  # CPU backend may not implement it
            mem = {"error": str(e)}

        per_kind, coll_b = hc["collectives"], hc["collective_bytes"]

        # roofline terms (seconds). cost_analysis is per-device post-SPMD.
        compute_t = flops / PEAK_FLOPS
        memory_t = bytes_accessed / HBM_BW
        collective_t = coll_b / ICI_BW
        terms = {"compute_s": compute_t, "memory_s": memory_t,
                 "collective_s": collective_t}
        dominant = max(terms, key=terms.get)

        # useful model flops (global): 6ND train (+IS scoring fwd) or
        # 2ND + attention-over-cache for serving — see counting.model_flops
        from repro.models.counting import model_flops as mf
        model_flops = mf(cfg, shape, variant,
                         presample_ratio=rec.get("presample_ratio", 3))
        rec.update({
            "ok": True,
            "chips": int(n_chips),
            "lower_s": round(t_lower - t0, 2),
            "compile_s": round(t_compile - t_lower, 2),
            "flops_per_chip": flops,
            "bytes_per_chip": bytes_accessed,
            "collective_bytes_per_chip": coll_b,
            "collectives": per_kind,
            "memory": mem,
            "terms": terms,
            "dominant": dominant,
            "model_flops_global": float(model_flops),
            "model_flops_per_chip": float(model_flops / n_chips),
            "useful_flop_frac": float(model_flops / n_chips / flops) if flops else None,
        })
        # roofline fraction: ideal step time is bounded below by the useful
        # compute AND by reading each live byte (args incl. weights, caches,
        # optimizer state) once from HBM. frac = ideal / achieved-roofline.
        arg_b = mem.get("argument_bytes") or 0
        ideal = max(model_flops / n_chips / PEAK_FLOPS, arg_b / HBM_BW)
        rec["ideal_s"] = ideal
        rec["roofline_frac"] = float(ideal / max(terms.values())) \
            if max(terms.values()) > 0 else None
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-3000:]
        print(f"[FAIL] {cell_id}: {rec['error']}", flush=True)
    out_path.write_text(json.dumps(rec, indent=2))
    status = "ok" if rec["ok"] else "FAIL"
    print(f"[{status}] {cell_id} lower={rec.get('lower_s')}s "
          f"compile={rec.get('compile_s')}s dominant={rec.get('dominant')}",
          flush=True)
    return rec


def default_cells(meshes=("pod", "multipod")):
    from repro.configs import ARCHS, get_config
    from repro.configs.base import applicable_shapes
    cells = []
    for arch in ARCHS:
        if arch.startswith("lm-"):
            continue
        cfg = get_config(arch)
        for shape in applicable_shapes(cfg):
            variant = "is_fused" if shape.kind == "train" else "serve"
            for mk in meshes:
                cells.append((arch, shape.name, mk, variant))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--variant", default=None,
                    help="is_chunked | is_naive | uniform | serve")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--meshes", default="pod,multipod")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    if args.all:
        cells = default_cells(tuple(args.meshes.split(",")))
        print(f"dry-run sweep: {len(cells)} cells", flush=True)
        n_fail = 0
        for c in cells:
            rec = run_cell(*c, force=args.force)
            n_fail += 0 if rec.get("ok") else 1
        print(f"done; {n_fail} failures", flush=True)
        sys.exit(1 if n_fail else 0)

    variant = args.variant or ("serve" if args.shape != "train_4k" else "is_fused")
    rec = run_cell(args.arch, args.shape, args.mesh, variant, force=args.force)
    sys.exit(0 if rec.get("ok") else 1)


if __name__ == "__main__":
    main()
