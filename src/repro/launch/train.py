"""Production training launcher — a thin shell over ``repro.api``.

    python -m repro.launch.train --arch yi-34b --shape... via dotted \
        overrides --mesh pod --ckpt_dir gs://.../run1     # on a real pod
    python -m repro.launch.train --arch lm-tiny --smoke   # 1-device CPU (CI)

Flags are the auto-generated config CLI (``Experiment.from_flags``):
reserved ``--arch/--preset/--smoke/--mesh/--source`` plus dotted
``RunConfig`` overrides, e.g.::

    --steps 2000 --optim.lr=3e-4 --imp.presample_ratio=5 \
    --sampler.scheme=history --imp.overlap_scoring=false \
    --data.prefetch_depth=3 --data.device_put=true \
    --ckpt_dir gs://.../run1 --ckpt_every=100

Unknown keys are hard errors — there is no launcher-local argparse copy
to drift out of sync.

On a multi-host pod each host runs this same command; jax.distributed is
initialised from the cluster environment (SLURM, a coordinator address,
or a TPU worker list naming more than one host). A single host needs no
initialisation: its one process drives every local chip. Every
host derives the identical ``BatchPlan`` per step (the selection plane —
shared PRNG over the global index space, global score reads through the
strided all-gather) and materialises only its data-parallel row slice;
the depth-N ``DataPlane`` (``--data.prefetch_depth``) pipelines plan →
gather → device-put behind the update step. Mesh, shardings, IS train
step, checkpointing and straggler handling all come from the library —
this file only wires CLI → Experiment → fit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO = Path(__file__).resolve().parents[3]


def cluster_env():
    """The environment variable that makes this launch one process of
    several, or None. A TPU worker list with one host is a single-host
    machine, not a cluster."""
    for var in ("COORDINATOR_ADDRESS", "SLURM_JOB_ID"):
        if os.environ.get(var):
            return var
    hosts = [h for h in os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",")
             if h.strip()]
    return "TPU_WORKER_HOSTNAMES" if len(hosts) > 1 else None


def maybe_init_distributed():
    """Initialise multi-host JAX when launched as one of several processes."""
    if cluster_env() is None:
        return False
    jax.distributed.initialize()
    return True


def configure_compile_cache() -> str:
    """The persistent compilation cache directory: JAX's own
    ``JAX_COMPILATION_CACHE_DIR`` where it is set (JAX reads it itself),
    else the fixed ``<repo>/.jax_cache`` — a fixed path, because the path
    is part of what a cache hit needs."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def main(argv=None):
    """CLI → Experiment → fit; returns ``(experiment, history)``."""
    print(f"[repro] compile cache: {configure_compile_cache()}", flush=True)
    maybe_init_distributed()
    from repro.api import Experiment, LoggingHook
    exp = Experiment.from_flags(argv)
    _, history = exp.fit(hooks=[LoggingHook(every=10)])
    return exp, history


if __name__ == "__main__":
    main()
