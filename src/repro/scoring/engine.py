"""The decoupled scoring engine.

The paper's speedup criterion (§3.3: B + 3b < 3τb) prices the scoring pass
at ONE forward per candidate — it only holds if scoring really is that
cheap. Welded into the update step (the pre-refactor layout) the scoring
pass inherits everything the update path needs and the score path doesn't:
remat, full-precision compute, grad plumbing, and the update's sharding.
``ScoreEngine`` owns a standalone jitted score function with none of that:

* forward-only — no ``value_and_grad``, no remat (nothing is rematerialised
  because nothing is retained);
* ``score_dtype`` compute — floating params are cast down (bf16 by
  default) before the forward; scores rank samples, they don't train them;
* the fused ``ce_score`` reduction (``imp.score_impl``) for the per-token
  statistics;
* batch-only sharding specs — the batch axis shards over ("pod","data"),
  params keep whatever (committed) layout they already have.

Because the engine is its own dispatch unit, the trainer can launch
scoring for batch k+1 while batch k's update runs (double-buffering — see
``repro.api.loop``), and host-side samplers can refresh the
persistent ``ScoreStore`` out-of-band (``Sampler.refresh_scores``).
Scores used one step late are slightly stale; selection tolerates that
(Jiang et al. 2019) and the τ-gate maths is unchanged.

Multi-host: ``gather_scores`` is the host-side all-gather hook that turns
this host's score shard into the global vector the score-memory schemes
key on (ROADMAP "multi-host score-gather" item).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.distributed import sharding as shd


class ScoreEngine:
    """Standalone forward-only scorer for one ``LM`` under one run config."""

    def __init__(self, lm, run_cfg, mesh=None):
        self.lm = lm
        self.run = run_cfg
        self.mesh = mesh
        icfg = run_cfg.imp
        self.score_impl = icfg.score_impl
        sd = getattr(icfg, "score_dtype", None)
        self.score_dtype = None if sd in (None, "", "none") else sd
        self._jitted = {}       # batch structure -> jitted fn
        self._take = jax.jit(
            lambda pool, idx: {k: jnp.take(v, idx, axis=0)
                               for k, v in pool.items()})

    # -- the score function itself (pure; dryrun lowers this AOT) -----------
    def fwd(self, params, batch):
        """(params, batch) -> (per_sample_loss, per_sample_score); one
        forward pass, ``score_dtype`` compute, no grads, no remat."""
        loss_ps, scores = self.lm.sample_stats(
            params, batch, score_impl=self.score_impl,
            score_dtype=self.score_dtype)
        return (loss_ps.astype(jnp.float32),
                jax.lax.stop_gradient(scores.astype(jnp.float32)))

    # -- jit cache -----------------------------------------------------------
    def _key(self, batch):
        return tuple(sorted((k, tuple(v.shape), str(v.dtype))
                            for k, v in batch.items()))

    def _fn(self, batch):
        key = self._key(batch)
        fn = self._jitted.get(key)
        if fn is None:
            # a new batch structure costs an XLA compile; a growing count
            # mid-run means shape churn on the scoring path
            obs.counter("engine.jit_compiles").inc()
            if self.mesh is not None:
                bspecs = shd.batch_specs(
                    self.lm.cfg, jax.eval_shape(lambda: batch), self.mesh)
                named = shd.to_named(bspecs, self.mesh)
                # batch-only shardings: params ride on their committed layout
                fn = jax.jit(self.fwd, in_shardings=(None, named),
                             compiler_options=shd.compiler_options(self.mesh))
            else:
                fn = jax.jit(self.fwd)
            fn = shd.under_mesh(fn, self.mesh)
            self._jitted[key] = fn
        return fn

    # -- dispatch ------------------------------------------------------------
    def score(self, params, batch):
        """Launch the score pass; returns (loss_ps, scores) device arrays
        WITHOUT blocking — jax dispatch is async, so the caller can overlap
        this with other device work and materialise later."""
        obs.counter("engine.dispatches").inc()
        # the span covers dispatch cost only, not compute — the pass is
        # async; a fat span here means host-side tracing/transfer overhead
        with obs.span("engine.dispatch"):
            batch = self._to_device(batch)
            return self._fn(batch)(params, batch)

    def score_chunked(self, params, batch):
        """Chunk-accumulated scoring, nothing pruned: the conservative
        mode's host-path twin. Survivor scores of the PRUNED pass are
        bitwise the unpruned chunked pass's (same per-row accumulation
        order — row slicing doesn't change it), so a host that scores its
        candidate slice through this entry emits plan bytes identical to
        a host running the pruned device pass. Same async contract as
        ``score``; the fut is the pruned pass's 4-tuple (alive all ones,
        zero tiles skipped)."""
        obs.counter("engine.dispatches").inc()
        with obs.span("engine.dispatch"):
            batch = self._to_device(batch)
            rows = int(batch["labels"].shape[0])
            # k = rows hits the degenerate no-prune branch: full chunked
            # scoring; the (unused) race context is pinned to 0
            return self._fn_pruned(batch, rows)(params, batch,
                                                jnp.uint32(0))

    def _to_device(self, batch):
        """jnp.asarray every value, charging anything that actually crosses
        the host boundary to ``engine.h2d_bytes`` (already-device arrays are
        free — that counter difference is the fused path's transfer claim)."""
        h2d = sum(np.asarray(v).nbytes for v in batch.values()
                  if not isinstance(v, jax.Array))
        if h2d:
            obs.counter("engine.h2d_bytes").inc(h2d)
        return {k: jnp.asarray(v) for k, v in batch.items()}

    def _fn_pruned(self, batch, k: int):
        """Jit cache for the survival-pruned pool pass, keyed on (batch
        structure, race k). The hash context rides as a TRACED uint32 —
        it changes every step and must not retrigger compilation."""
        key = (self._key(batch), int(k))
        fn = self._jitted.get(key)
        if fn is None:
            obs.counter("engine.jit_compiles").inc()

            def pruned(params, batch, ctx):
                loss_ps, scores, alive, stats = self.lm.pool_stats_pruned(
                    params, batch, ctx, k=k, score_dtype=self.score_dtype)
                return (loss_ps.astype(jnp.float32),
                        jax.lax.stop_gradient(scores.astype(jnp.float32)),
                        alive, stats)
            fn = shd.under_mesh(jax.jit(
                pruned, compiler_options=shd.compiler_options(self.mesh)),
                self.mesh)
            self._jitted[key] = fn
        return fn

    # -- fused presample entries ---------------------------------------------
    def score_select(self, params, batch, prune=None):
        """Device-resident scoring for the fused presample path: push the
        candidate pool up ONCE, dispatch the score pass on it, and keep the
        device refs so the winners can later be gathered on-chip
        (``take_rows``) instead of re-uploaded from host. Returns
        ``{"pool": device batch, "fut": (loss_ps, scores)}`` — same async
        non-blocking contract as ``score``.

        ``prune={"ctx": ..., "k": ...}`` routes through the survival-pruned
        chunked pass (``LM.pool_stats_pruned``): rows that already lost the
        step's race stop being scored, and ``fut`` grows to (loss_ps,
        scores, alive, prune_stats)."""
        pool = self._to_device(batch)
        obs.counter("engine.dispatches").inc()
        with obs.span("engine.dispatch"):
            if prune is not None:
                ctx = jnp.asarray(np.uint32(int(prune["ctx"]) & 0xFFFFFFFF))
                fut = self._fn_pruned(pool, int(prune["k"]))(params, pool,
                                                            ctx)
            else:
                fut = self._fn(pool)(params, pool)
        return {"pool": pool, "fut": fut}

    def take_rows(self, handle, idx, weights=None):
        """On-device row gather of the selection out of a ``score_select``
        pool: only the (b,) index vector (and optional per-row weights)
        cross the host boundary; the rows themselves never left the chip."""
        obs.counter("engine.row_gathers").inc()
        with obs.span("engine.take_rows"):
            idx = np.ascontiguousarray(np.asarray(idx, np.int32))
            h2d = idx.nbytes + (0 if weights is None
                                else np.asarray(weights).nbytes)
            obs.counter("engine.h2d_bytes").inc(h2d)
            batch = dict(self._take(handle["pool"], jnp.asarray(idx)))
            if weights is not None:
                batch["weights"] = jnp.asarray(
                    np.asarray(weights, np.float32))
            return batch

    def score_host(self, params, batch):
        """Blocking convenience: numpy (loss_ps, scores)."""
        loss_ps, scores = self.score(params, batch)
        return (np.asarray(jax.device_get(loss_ps)),
                np.asarray(jax.device_get(scores)))

    # -- the selection plane's scoring entry ---------------------------------
    def score_plan(self, params, plan, assembler):
        """Score THIS host's row slice of a ``BatchPlan`` (forward-only,
        async — same non-blocking contract as ``score``). The host-side
        refresh path is keyed by plans: the assembler materialises exactly
        this host's data-parallel shard, and the caller stitches the row
        shards back together (``Sampler._gather_rows`` over
        ``collectives.allgather_rows``) before merging into the
        ``ScoreStore``."""
        return self.score(params, assembler.assemble(plan))

    # -- multi-host gather hook ----------------------------------------------
    def gather_scores(self, local_scores, *, host_id=None, n_hosts=None,
                      n_global=None):
        """Host-local score shard -> global score vector (identity when
        single-process). See ``distributed.collectives.gather_host_scores``."""
        from repro.distributed.collectives import gather_host_scores
        return gather_host_scores(local_scores, host_id=host_id,
                                  n_hosts=n_hosts, n_global=n_global)
