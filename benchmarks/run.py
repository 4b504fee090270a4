"""Benchmark orchestrator: ``PYTHONPATH=src python -m benchmarks.run``.

One function per paper table/figure (+ the roofline report). Prints
``name,us_per_call,derived`` CSV lines; artifacts land in
benchmarks/artifacts/. Training-loop suites run through the public
``repro.api`` facade — there is no benchmark-local trainer wiring.

Suites in ``ARTIFACTS`` own a committed JSON artifact: after a suite
"succeeds", the orchestrator verifies the file was actually (re)written
this run and fails LOUDLY otherwise — a suite that silently returns
without its artifact is how BENCH_*.json files go stale or missing.

Subsets: ``python -m benchmarks.run fig1 fig3 roofline``
"""
from __future__ import annotations

import sys
import time
import traceback

# suite -> the artifact (benchmarks/artifacts/<name>.json) it must write
ARTIFACTS = {
    "sampler": "BENCH_sampler",
    "pipeline": "BENCH_pipeline",
    "fused": "BENCH_fused",
    "selection": "BENCH_selection",
    "scoring_overlap": "BENCH_scoring",
    "score_prune": "BENCH_prune",
}


def main() -> None:
    from benchmarks import paper_figures as pf
    from benchmarks import (data_plane, fused_presample, roofline,
                            sampler_compare, score_prune, scoring_overhead,
                            selection_scale, svrg_compare)
    from benchmarks.common import ART

    suites = {
        "sampler": sampler_compare.sampler_compare,
        "pipeline": data_plane.bench_data_plane,
        "fused": fused_presample.bench_fused_presample,
        "selection": selection_scale.bench_selection_scale,
        "fig1": pf.fig1_variance_reduction,
        "fig2": pf.fig2_correlation,
        "fig3": pf.fig3_convergence,
        "fig4": pf.fig4_finetune,
        "fig5": pf.fig5_sequence,
        "fig7": pf.fig7_ablation_B,
        "tau": pf.tau_gate_behaviour,
        "scoring": scoring_overhead.scoring_overhead,
        "scoring_overlap": scoring_overhead.bench_scoring_overlap,
        "score_prune": score_prune.bench_score_prune,
        "svrg": svrg_compare.svrg_compare,
        "roofline": lambda: roofline.render(emit=print),
    }
    wanted = sys.argv[1:] or list(suites)
    unknown = [w for w in wanted if w not in suites]
    if unknown:
        raise SystemExit(f"unknown suites {unknown}; have {sorted(suites)}")
    print("name,us_per_call,derived")
    failures = 0
    for name in wanted:
        t0 = time.time()
        try:
            suites[name]()
            art = ARTIFACTS.get(name)
            if art is not None:
                path = ART / f"{art}.json"
                if not path.exists() or path.stat().st_mtime < t0 - 1:
                    raise RuntimeError(
                        f"suite '{name}' completed without writing "
                        f"{path} — artifact contract broken")
            print(f"{name}.elapsed_s,,{time.time() - t0:.1f}", flush=True)
        except Exception as e:
            failures += 1
            print(f"{name}.ERROR,,{type(e).__name__}: {e}", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
