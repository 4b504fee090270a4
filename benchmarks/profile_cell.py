"""Dry-run profiler: per-op cost breakdown (trip-count-multiplied) for one
(arch × shape × mesh × variant) cell. The §Perf loop's 'profile'.

    PYTHONPATH=src python -m benchmarks.profile_cell gemma3-12b train_4k \
        pod is_fused
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import sys
from collections import defaultdict

import jax


def profile(arch, shape, mesh_kind="pod", variant="is_fused", topn=25):
    from repro.launch.dryrun import build_cell
    from repro.launch import hlo_cost as hc

    mesh, fn, args, meta, _score = build_cell(arch, shape, mesh_kind, variant)
    with jax.set_mesh(mesh):
        compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    comps, entry = hc.parse_hlo(text)

    shape_of = {c: {op["name"]: op["result"] for op in ops}
                for c, ops in comps.items()}

    # per-op accumulation with trip multipliers
    rows_bytes = defaultdict(float)
    rows_flops = defaultdict(float)
    rows_coll = defaultdict(float)

    def operand_bytes(cn, t, individually=False):
        out = []
        for m in hc._NAME_RE.finditer(t):
            shp = shape_of.get(cn, {}).get(m.group(1))
            if shp:
                out.append(hc._shape_elems_bytes(shp)[1])
        return out if individually else sum(out)

    def walk(cn, mult):
        for op in comps.get(cn, ()):
            o = op["op"]
            if o == "while":
                import re
                mcond = re.search(r"condition=%?([\w.\-]+)", op["attrs"])
                mbody = re.search(r"body=%?([\w.\-]+)", op["attrs"])
                trips = hc._trip_count(comps.get(mcond.group(1), ()))
                walk(mbody.group(1), mult * trips)
                continue
            if o in ("call", "conditional"):
                for c in op["called"]:
                    walk(c, mult)
                continue
            if o == "fusion":
                for c in op["called"]:
                    walk(c, mult)
            key = op["attrs"].split("op_name=\"")
            tag = key[1].split("\"")[0][-80:] if len(key) > 1 else op["name"]
            if o in ("dot", "convolution"):
                first = hc._NAME_RE.search(op["operands"])
                lhs = shape_of.get(cn, {}).get(first.group(1)) if first else None
                rows_flops[f"{o}:{tag}"] += mult * hc._dot_flops(
                    op["result"], lhs, op["attrs"])
            base = o.split("-start")[0]
            if base in hc.COLLECTIVES and not o.endswith("-done"):
                rows_coll[f"{base}:{tag}"] += mult * hc._shape_elems_bytes(
                    op["result"])[1]
            b = hc._bytes_for_op(
                op, lambda t, individually=False: operand_bytes(cn, t, individually),
                lambda t: hc._shape_elems_bytes(t)[1])
            if b:
                rows_bytes[f"{o}:{tag}"] += mult * b

    walk(entry, 1)

    def top(d, n=topn):
        return sorted(d.items(), key=lambda kv: -kv[1])[:n]

    print(f"=== {arch} {shape} {mesh_kind} {variant} ===")
    print("-- top bytes (GB, trip-multiplied, per chip) --")
    for k, v in top(rows_bytes):
        print(f"{v / 1e9:10.2f}  {k}")
    print("-- top flops (GF) --")
    for k, v in top(rows_flops, 12):
        print(f"{v / 1e9:10.1f}  {k}")
    print("-- top collectives (GB) --")
    for k, v in top(rows_coll, 15):
        print(f"{v / 1e9:10.3f}  {k}")


if __name__ == "__main__":
    profile(*sys.argv[1:])
