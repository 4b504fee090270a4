"""Pluggable example-selection schemes behind one ``Sampler`` API.

Every scheme is a PLANNER on the selection plane: it emits a device-free
``BatchPlan`` (``repro.data.plan``) — the global example ids of every row
of the step's global batch, plus proposal probs / unbiasedness weights —
computed identically on all hosts from a shared PRNG keyed on
``(seed, scheme salt, step)`` over the GLOBAL index space. Store-backed
schemes read the global score vector through the strided all-gather
(``ScoreStore.global_scores``), so multi-host runs select from the
paper's global ∝ ĝ distribution instead of a biased per-host mixture.
The ``Assembler`` (``repro.sampler.assembly``) then materialises each
host's contiguous row slice of the plan — its data-parallel shard.

The trainer's loop is scheme-agnostic and split in two phases so scoring
can overlap the update step:

    handle = sampler.begin(pstate, step, params)              # may launch
    batch, plan, pstate' = sampler.finish(handle, params)     # host side
    state, metrics = step_fn(state, batch[, plan.is_flag])    # device side
    sampler.observe(plan, metrics["sample_scores"])           # feedback

``begin``/``finish`` degrade to a synchronous ``next_batch`` for schemes
that don't score out-of-band; schemes whose plans are pure functions of
the pipeline cursor (``plan_is_pure``) additionally let the depth-N
``DataPlane`` pre-plan and pre-gather batches on worker threads.

Schemes:

* ``uniform`` — sequential batches of b, plain SGD. Still feeds scores
  into the store (free), so switching schemes mid-run starts warm.
* ``presample`` — the paper's Algorithm 1: plans of B = ratio·b
  sequential candidates, the device scores and resamples; the τ
  controller lives on device (``repro.core.is_train.build_step``).
* ``presample`` + ``host_score`` — the same Algorithm 1 but the scoring
  pass runs on the decoupled ``repro.scoring.ScoreEngine`` path and
  selection happens on host: each host scores its candidate row slice,
  the row shards are all-gathered, and the (shared-PRNG) selection plan
  reuses the already-materialised candidate rows via ``plan.src_rows``.
  The ``ScoreStore`` is refreshed out-of-band with ALL B candidate
  scores every step (``HostPresampleSampler``).
* ``presample`` + ``imp.presample_impl="fused"`` — the host path's twin
  with the candidate pool kept device-resident: the engine scores it in
  place and the winners are gathered on-chip; only the (B,) score vector
  and the (b,) selection cross the host boundary, and the plans are
  bitwise identical to the host path's (``FusedPresampleSampler``).
* ``history`` — dataset-level importance sampling from the persistent
  score memory: draw b GLOBAL ids ∝ the smoothed/sharpened GLOBAL store
  distribution, attach unbiased weights 1/(n·pᵢ), zero scoring overhead.
  The τ-of-the-store gate switches it on only once the memory is warm
  (coverage) and concentrated enough to pay (τ > τ_th).
* ``selective`` — Biggest-Losers-style selective backprop: rank a
  sequential candidate window by the GLOBAL stored scores, train on the
  global top-b (unseen ids rank highest, so everything is visited).
  Deliberately biased — no weights.

Selection implementations (``imp.selection_impl``): store-backed schemes
(``history`` / ``selective``) read the score memory either through the
full O(n) strided gather (``"gather"``, exact PR-4 semantics) or through
the sharded O(b) path (``"sharded"``, default): Gumbel/exponential-key
top-k candidate exchange plus O(1) sufficient-stat collectives — see
``repro.sampler.selection``.

Multi-host note: under a true multi-process launch the collectives ride
``jax.experimental.multihost_utils`` (coordination-service fallback on
CPU); a SIMULATED multi-host run (tests) injects ``sampler.gather_fn``
(strided score gather), ``sampler.row_gather_fn`` (contiguous row-shard
gather), ``sampler.reduce_fn`` (sufficient-stat allreduce) and
``sampler.topk_fn`` (candidate exchange) instead.
"""
from __future__ import annotations

import jax
import numpy as np

from repro import obs
from repro.data.pipeline import PipelineState
from repro.data.plan import BatchPlan
from repro.sampler import selection
from repro.sampler.assembly import Assembler
from repro.sampler.store import ScoreStore


class Sampler:
    """Base: sequential planning + score-memory bookkeeping."""

    scheme = "base"
    uses_score_step = True   # False → the paper's on-device presample step
    plan_is_pure = True      # plan() reads only (pstate, step) → the
                             # DataPlane may pre-plan ahead of consumption

    def __init__(self, run_cfg, source, assembler=None):
        self.cfg = run_cfg.sampler
        self.icfg = run_cfg.imp
        self.b = run_cfg.shape.global_batch
        self.seed = run_cfg.seed
        self.source = source
        self.host_id = getattr(source, "host_id", 0)
        self.n_hosts = getattr(source, "n_hosts", 1)
        self.store = ScoreStore(source.n, host_id=self.host_id,
                                n_hosts=self.n_hosts, ema=self.cfg.ema,
                                staleness=self.cfg.staleness)
        self.assembler = assembler or Assembler(source)
        self._epoch = np.zeros((), np.int64)
        self.engine = None       # repro.scoring.ScoreEngine (bind_engine)
        # "auto" resolves from the measured BENCH_selection crossover;
        # the counter records the resolved impl once per run for the logs
        self.impl = selection.resolve_selection_impl(
            self.icfg.selection_impl, n=source.n, b=self.b,
            n_hosts=self.n_hosts)
        obs.counter(f"sampler.selection_impl.{self.impl}").inc()
        # simulated multi-host runs inject these; None → the production
        # multihost_utils collectives (identity when n_hosts == 1)
        self.gather_fn = None       # strided store-shard gather
        self.row_gather_fn = None   # contiguous row-shard gather
        self.reduce_fn = None       # sufficient-stat allreduce (sharded)
        self.topk_fn = None         # candidate-block exchange (sharded)

    # global rows the device step sees per plan
    @property
    def fetch_size(self) -> int:
        return self.b

    def _tick_epoch(self, epoch: int) -> None:
        if int(self._epoch) != int(epoch):
            # decay toward the GLOBAL seen mean: per-shard means would make
            # the per-host score views drift apart at every epoch boundary
            self.store.decay(self._global_seen_mean())
            self._epoch = np.asarray(epoch, np.int64)

    def _reduce_stats(self, temperature: float) -> np.ndarray:
        """Global sufficient stats [Σs_seen, #seen, Σs̃, Σs̃²] — the O(1)
        collective the sharded path reads instead of the full vector.
        An injected ``reduce_fn`` (simulated multi-host) receives the
        per-shard stats builder and applies it to every in-process store
        at this lockstep point."""
        def local_stats(store):
            return selection.shard_stats(store.scores, store.seen,
                                         temperature)
        if self.reduce_fn is not None:
            return np.asarray(self.reduce_fn(local_stats), np.float64)
        from repro.distributed.collectives import allreduce_stats
        return np.asarray(allreduce_stats(local_stats(self.store),
                                          n_hosts=self.n_hosts), np.float64)

    def _global_seen_mean(self):
        if self.impl == "sharded":
            # staleness-decay attractor from the O(1) stats allreduce —
            # no O(n) gather at the epoch tick
            stats = self._reduce_stats(1.0)
            return float(stats[0] / stats[1]) if stats[1] else None
        if self.n_hosts == 1:
            return None                   # local mean IS the global mean
        sg = self.store.global_scores(self.gather_fn)
        m = sg >= 0
        return float(sg[m].mean()) if m.any() else None

    def notify_consumed(self, plan: BatchPlan) -> None:
        """Epoch bookkeeping at CONSUMPTION time — the DataPlane calls
        this as plans leave the pipeline, so staleness decay fires when
        training crosses an epoch, not when a worker thread pre-plans
        past one."""
        self._tick_epoch(plan.epoch)

    # -- planning (the selection plane) ---------------------------------------
    def plan(self, pstate: PipelineState, step: int):
        """Emit (plan, pstate') for ``step``. MUST be identical on every
        host: pure index math + shared PRNG + globally-gathered reads."""
        gids = self.source.global_indices(pstate, self.fetch_size)
        plan = BatchPlan(step=step, epoch=pstate.epoch, gids=gids)
        return plan, pstate.advance(self.fetch_size, self.source.n)

    def next_batch(self, pstate: PipelineState, step: int):
        self._tick_epoch(pstate.epoch)
        plan, nxt = self.plan(pstate, step)
        return self.assembler.assemble(plan), plan, nxt

    # -- two-phase API (overlapped scoring) -----------------------------------
    def begin(self, pstate: PipelineState, step: int, params=None):
        """Phase 1: start producing the batch for ``step``. Engine-backed
        schemes launch their (async) scoring pass here so it overlaps
        whatever device work is in flight; the base scheme just records
        where to resume."""
        return {"pstate": pstate, "step": step}

    def finish(self, handle, params=None):
        """Phase 2: materialise (batch, plan, pstate'). ``params`` is used
        only if ``begin`` didn't already score (the synchronous path)."""
        return self.next_batch(handle["pstate"], handle["step"])

    # -- decoupled scoring engine ---------------------------------------------
    def bind_engine(self, engine) -> None:
        """Attach a ``repro.scoring.ScoreEngine`` (host-side scoring and
        out-of-band store refresh route through it)."""
        self.engine = engine

    def _gather_rows(self, local_scores, n_rows: int) -> np.ndarray:
        """Row-sharded score vector -> global (identity single-host)."""
        local = np.asarray(local_scores, np.float32).reshape(-1)
        if self.n_hosts == 1:
            return local[:n_rows]
        from repro.distributed.collectives import allgather_rows
        gather = self.row_gather_fn or allgather_rows
        return np.asarray(gather(local, n_rows=n_rows,
                                 n_hosts=self.n_hosts), np.float32)

    def refresh_plan(self, params, plan: BatchPlan) -> int:
        """Out-of-band ``ScoreStore`` refresh keyed by a plan: each host
        scores ITS row slice through the engine's forward-only path, the
        row shards are gathered, and every host merges the full vector
        (the store drops unowned ids). Returns slots written locally."""
        if self.engine is None:
            raise RuntimeError("no ScoreEngine bound (call bind_engine)")
        fut = self.engine.score_plan(params, plan, self.assembler)
        local = np.asarray(jax.device_get(fut[1]), np.float32)
        scores = self._gather_rows(local, plan.n_rows)
        return self.store.update(plan.gids, scores)

    def refresh_scores(self, params, gids, epoch: int = 0) -> int:
        """Back-compat wrapper: score arbitrary example ids (one plan)."""
        gids = np.asarray(gids, np.int64)
        return self.refresh_plan(params, BatchPlan(step=-1, epoch=epoch,
                                                   gids=gids))

    def observe(self, plan, scores) -> None:
        """Close the feedback loop: the step's (global) score vector for
        the plan's rows merges into the store (unowned ids dropped)."""
        lo, hi = plan["rows"]
        self.store.update(plan["gids"], np.asarray(scores)[lo:hi])

    def stats(self) -> dict:
        return {"store_coverage": self.store.coverage()}

    # -- checkpoint -----------------------------------------------------------
    def state_dict(self) -> dict:
        return {"store": self.store.state_dict(), "epoch": self._epoch}

    def load_state_dict(self, d) -> None:
        self.store.load_state_dict(d["store"])
        self._epoch = np.asarray(d["epoch"], np.int64).reshape(())


class UniformSampler(Sampler):
    scheme = "uniform"


class PresampleSampler(Sampler):
    """Algorithm 1's data side: plans of B = ratio·b candidates; scoring,
    τ gating, and resampling happen inside the jitted train step."""

    scheme = "presample"
    uses_score_step = False

    @property
    def fetch_size(self) -> int:
        return self.b * self.icfg.presample_ratio


class HostPresampleSampler(Sampler):
    """Algorithm 1 with the scoring pass on the decoupled engine path.

    Per step: plan B = ratio·b sequential candidates, assemble THIS
    host's candidate row slice, score it with the ``ScoreEngine``
    (forward-only, ``score_dtype``, no remat — launched in ``begin`` so
    it can overlap the previous update), all-gather the row-sharded
    scores, τ-gate on a host-side EMA mirroring the on-device controller,
    and either draw the b-of-B race-WOR sample ∝ Ĝ with the
    Horvitz–Thompson unbiasedness weights (IS phase — hash-keyed, shared
    with the fused device selection kernel: ``selection.
    presample_race_select``) or take the first b with unit weights
    (uniform phase). The selection plan records ``src_rows`` so the
    assembler reuses the already-materialised candidate rows. ALL B
    candidate scores refresh the ``ScoreStore`` out-of-band, so the
    memory warms ratio× faster than training alone.

    Candidate scoring is always a uniform (sequential) draw, so — unlike
    the host-chosen score-memory schemes — every step refreshes τ. The
    gathered score vector and the shared selection PRNG make the
    selection plan bitwise identical on every host.
    """

    scheme = "presample_host"
    plan_is_pure = False     # the selection plan needs engine scores
    SALT = 4211              # the scheme's shared-PRNG / hash salt

    def __init__(self, run_cfg, source, assembler=None):
        super().__init__(run_cfg, source, assembler)
        self.B = self.b * self.icfg.presample_ratio
        self.tau_th = self.icfg.resolved_tau_th(self.b)
        self.tau_ema = np.zeros((), np.float64)
        self.overlap = bool(self.icfg.overlap_scoring)
        # survival-pruned scoring: loser rows stop being scored mid-pool,
        # so ALL presample paths switch to the survivor-closed plan math
        # (raw race keys + HT-estimated τ̂ — selection.
        # presample_race_select_raw); "off" is the PR-7 byte-exact path
        self.prune = (getattr(self.icfg, "score_prune", "off")
                      == "conservative")
        self._sp_pull = obs.span("sampler.pull_scores")
        self._sp_select = obs.span("sampler.select")

    @property
    def active(self) -> bool:
        return bool(self.tau_ema > self.tau_th)

    def candidate_plan(self, pstate: PipelineState, step: int):
        """The (pure) B-candidate plan selection is carved out of."""
        gids = self.source.global_indices(pstate, self.B)
        plan = BatchPlan(step=step, epoch=pstate.epoch, gids=gids)
        return plan, pstate.advance(self.B, self.source.n)

    def begin(self, pstate: PipelineState, step: int, params=None):
        self._tick_epoch(pstate.epoch)
        cplan, nxt = self.candidate_plan(pstate, step)
        cands = self.assembler.assemble(cplan)
        handle = {"pstate": pstate, "step": step, "cplan": cplan,
                  "cands": cands, "nxt": nxt, "fut": None}
        if self.overlap and params is not None and self.engine is not None:
            # async dispatch: runs behind whatever update is in flight.
            # Conservative mode scores through the chunked pass (nothing
            # pruned on the host path) so this host's score bytes equal
            # the pruned device pass's survivor bytes — plan equality
            # across paths is byte-level, so the accumulation order must
            # be too.
            handle["fut"] = (self.engine.score_chunked(params, cands)
                             if self.prune
                             else self.engine.score(params, cands))
        return handle

    def finish(self, handle, params=None):
        fut = handle["fut"]
        if fut is None:           # synchronous path (overlap off / no params)
            if self.engine is None:
                raise RuntimeError(
                    "presample_host scores through the decoupled engine — "
                    "call bind_engine(ScoreEngine(...)) first")
            if params is None:
                raise RuntimeError(
                    "presample_host needs params to score: pass them to "
                    "begin() (overlapped) or finish() (synchronous)")
            fut = (self.engine.score_chunked(params, handle["cands"])
                   if self.prune
                   else self.engine.score(params, handle["cands"]))
        cplan = handle["cplan"]
        # every host scored only its candidate slice; the gathered vector
        # (identity single-host) is what makes selection globally agreed
        scores = self._gather_rows(self._pull_scores(fut), cplan.n_rows)
        with self._sp_select:
            plan = self._select_plan(cplan, scores, handle["step"])
        batch = self._materialize(handle, cplan, plan)
        return batch, plan, handle["nxt"]

    def _pull_scores(self, fut) -> np.ndarray:
        """Block on the score pass and bring THIS host's (B/H,) score
        shard down — the one pool-sized D2H transfer either presample
        path makes (the counter is the fused benchmark's evidence)."""
        with self._sp_pull:
            local = np.asarray(jax.device_get(fut[1]), np.float32)
            obs.counter("sampler.d2h_bytes").inc(local.nbytes)
            if len(fut) > 3:      # pruned pass: (loss, scores, alive, stats)
                self._record_prune_stats(fut[3])
            return local

    def _record_prune_stats(self, stats) -> None:
        """The pruned pass's flop receipt: [rows_killed, tiles_skipped,
        tiles_total, flops_saved] comes back as one tiny device vector
        (counted host-side — the jitted pass stays obs-free)."""
        st = np.asarray(jax.device_get(stats), np.float64)
        obs.counter("kernels.prune.rows_killed").inc(int(st[0]))
        obs.counter("kernels.prune.blocks_skipped").inc(int(st[1]))
        obs.counter("kernels.prune.tiles_total").inc(int(st[2]))
        obs.counter("kernels.prune.flops_saved").inc(int(st[3]))

    def _prune_spec(self, step):
        """The device pass's race parameters when survival pruning is on:
        the step's selection hash context (the exponential variates Eᵢ
        derive from it on device, bit-identically to the host race) and
        the race k. None on the unpruned path."""
        if not self.prune:
            return None
        return {"ctx": selection.hash_context(self.seed, self.SALT,
                                              int(step)),
                "k": self.b}

    def _select_plan(self, cplan, scores, step) -> BatchPlan:
        """Gathered (B,) fresh scores -> the step's selection plan. The
        ONE selection both the host and fused paths run, on identical
        score bytes — which is what makes their plans bitwise equal."""
        if self.prune:
            return self._select_plan_pruned(cplan, scores, step)
        # out-of-band refresh: every candidate's fresh score enters the
        # memory, trained on or not
        self.store.update(cplan.gids, scores)
        g = scores.astype(np.float64)
        g = g / max(g.sum(), 1e-20)
        tau = float(np.sqrt(self.B * np.square(g).sum()))
        # same first-observation seeding rule as the device controller
        self.tau_ema = np.asarray(
            tau if self.tau_ema == 0.0
            else self.icfg.ema * float(self.tau_ema)
            + (1.0 - self.icfg.ema) * tau, np.float64)
        if not self.active:
            rows = np.arange(self.b, dtype=np.int64)
            return BatchPlan(step=cplan.step, epoch=cplan.epoch,
                             gids=cplan.gids[:self.b], src_rows=rows,
                             weights=np.ones((self.b,), np.float32))
        ctx = selection.hash_context(self.seed, self.SALT, int(step))
        idx, g, w, _thr = selection.presample_race_select(
            scores, self.b, ctx=ctx)
        return BatchPlan(step=cplan.step, epoch=cplan.epoch,
                         gids=cplan.gids[idx], probs=g[idx], src_rows=idx,
                         weights=w, is_flag=max(float(self.tau_ema), 1.0))

    def _select_plan_pruned(self, cplan, scores, step) -> BatchPlan:
        """The survivor-closed plan math (``imp.score_prune=
        "conservative"``). Under pruning the losers' score bytes are
        understated partials, so nothing full-vector is trustworthy —
        every plan quantity must be a function of the race's top-(k+1)
        keys alone, which conservative pruning preserves bit-for-bit:

        * the RAW-key race (``selection.presample_race_select_raw``)
          selects the identical set (scale only shifts all keys), and its
          HT totals give τ̂ and probs_hat in place of the exact Σs forms;
        * τ̂ feeds the same EMA/seeding rule and gate as the exact τ;
        * the store refresh takes only the b winners' (exact) scores —
          loser partials never enter the memory;
        * the race runs EVERY step (warmup included) so the τ̂ controller
          sees the same signal cadence as the exact controller; the
          warmup plan itself is unchanged (first b, unit weights).

        Every presample path runs this same function on its score bytes
        — pruned fused, unpruned fused, and host_score plans stay
        bitwise identical within the mode."""
        ctx = selection.hash_context(self.seed, self.SALT, int(step))
        idx, probs_hat, w, _thr, tau_hat = \
            selection.presample_race_select_raw(scores, self.b, ctx=ctx)
        self.store.update(cplan.gids[idx], scores[idx])
        self.tau_ema = np.asarray(
            tau_hat if self.tau_ema == 0.0
            else self.icfg.ema * float(self.tau_ema)
            + (1.0 - self.icfg.ema) * tau_hat, np.float64)
        if not self.active:
            rows = np.arange(self.b, dtype=np.int64)
            return BatchPlan(step=cplan.step, epoch=cplan.epoch,
                             gids=cplan.gids[:self.b], src_rows=rows,
                             weights=np.ones((self.b,), np.float32))
        return BatchPlan(step=cplan.step, epoch=cplan.epoch,
                         gids=cplan.gids[idx], probs=probs_hat,
                         src_rows=idx, weights=w,
                         is_flag=max(float(self.tau_ema), 1.0))

    def _materialize(self, handle, cplan, plan):
        """Selection plan -> device-feedable batch; the host path reuses
        the already-materialised candidate rows on host."""
        return self.assembler.assemble(plan,
                                       parent=(cplan, handle["cands"]))

    def next_batch(self, pstate: PipelineState, step: int, params=None):
        return self.finish(self.begin(pstate, step, params), params)

    def stats(self) -> dict:
        return {"store_coverage": self.store.coverage(),
                "presample_tau": float(self.tau_ema),
                "sampler_active": float(self.active)}

    def state_dict(self) -> dict:
        d = super().state_dict()
        d["tau_ema"] = self.tau_ema
        return d

    def load_state_dict(self, d) -> None:
        super().load_state_dict(d)
        self.tau_ema = np.asarray(d.get("tau_ema", 0.0),
                                  np.float64).reshape(())


class FusedPresampleSampler(HostPresampleSampler):
    """Algorithm 1 with the candidate pool DEVICE-RESIDENT end to end
    (``imp.presample_impl="fused"`` — repro.kernels.fused_presample).

    Same planning, τ controller, selection (``_select_plan``) and
    checkpoint state as the host path — the plans are bitwise identical
    by construction — but the data moves differently:

    * the pool is uploaded ONCE (``engine.score_select`` keeps the device
      refs; under a pipelined ``DataPlane`` the upload itself happens on
      the plane's device-put worker, off the critical path);
    * only the (B,) score vector comes down (τ/selection/ScoreStore live
      on host — checkpointed f64 state);
    * the winning rows are gathered ON DEVICE (``engine.take_rows``) —
      never re-uploaded from host.

    Single-host, its candidate plans are pure cursor math, so the plane
    pre-plans AND pre-gathers the expensive B-row pools on worker threads
    (the ``begin_finalize``/``finish_finalize`` protocol — the host path
    assembles B rows synchronously inside ``begin``). Multi-host it
    degrades to the parent host path wholesale (row-sharded pools need
    the all-gathered selection anyway), keeping plan equality trivial.
    """

    scheme = "presample_fused"

    def __init__(self, run_cfg, source, assembler=None):
        super().__init__(run_cfg, source, assembler)
        self.plan_is_pure = (self.n_hosts == 1)

    @property
    def fetch_size(self) -> int:
        return self.B

    def plan(self, pstate: PipelineState, step: int):
        # what the DataPlane pre-plans/pre-gathers is the candidate POOL;
        # selection is carved out of it at finalize time
        return self.candidate_plan(pstate, step)

    def begin(self, pstate: PipelineState, step: int, params=None):
        if self.n_hosts > 1:
            return super().begin(pstate, step, params)
        self._tick_epoch(pstate.epoch)
        cplan, nxt = self.candidate_plan(pstate, step)
        cands = self.assembler.assemble(cplan)
        return self.begin_finalize(cplan, cands, nxt, params=params)

    # -- the DataPlane finalize protocol --------------------------------------
    def begin_finalize(self, cplan, pool, cursor, params=None):
        """Phase 1 over an already-materialised candidate pool: push it up
        and dispatch the (async) score pass so it runs behind whatever
        update is in flight. ``pool`` may already be device arrays (the
        plane's device-put worker) — then the upload here is free."""
        handle = {"step": cplan.step, "cplan": cplan, "cands": pool,
                  "nxt": cursor, "fut": None, "dev": None}
        if self.overlap and params is not None and self.engine is not None:
            sel = self.engine.score_select(
                params, pool, prune=self._prune_spec(cplan.step))
            handle["dev"], handle["fut"] = sel["pool"], sel["fut"]
        return handle

    def finish(self, handle, params=None):
        if "dev" not in handle:              # parent-path handle (multi-host)
            return super().finish(handle, params)
        if handle["fut"] is None:            # synchronous path (overlap off)
            if self.engine is None:
                raise RuntimeError(
                    "presample_fused scores through the decoupled engine — "
                    "call bind_engine(ScoreEngine(...)) first")
            if params is None:
                raise RuntimeError(
                    "presample_fused needs params to score: pass them to "
                    "begin() (overlapped) or finish() (synchronous)")
            sel = self.engine.score_select(
                params, handle["cands"],
                prune=self._prune_spec(handle["step"]))
            handle["dev"], handle["fut"] = sel["pool"], sel["fut"]
        return super().finish(handle, params)

    finish_finalize = finish

    def _materialize(self, handle, cplan, plan):
        if handle.get("dev") is None:
            return super()._materialize(handle, cplan, plan)
        # on-device gather out of the resident pool: the b winning rows
        # never cross the host boundary
        return self.engine.take_rows({"pool": handle["dev"]},
                                     plan.src_rows, plan.weights)


class HistorySampler(Sampler):
    """Dataset-level IS from the persistent score memory — sampled from
    the GLOBAL store distribution so every host draws the same plan.

    Two selection implementations (``imp.selection_impl``):

    * ``"gather"`` — reassemble the O(n) global vector (gate-cadence
      cached), sample b ids WITH replacement ∝ p, weights 1/(n·pᵢ).
    * ``"sharded"`` (default) — O(1) sufficient-stat collectives refresh
      the τ/coverage gate every plan, and the sample is the exponential-
      race (Gumbel) top-b over score shards with an O(b·H) candidate
      exchange: probability-proportional-to-p WITHOUT replacement, with
      the race-threshold Horvitz–Thompson weights keeping the estimator
      unbiased (``repro.sampler.selection``). Plan cost O(n/H + b·H)
      instead of O(n).
    """

    scheme = "history"
    plan_is_pure = False     # plans read the (mutable) score memory
    SALT = 9173              # the scheme's shared-PRNG / hash salt

    def __init__(self, run_cfg, source, assembler=None):
        super().__init__(run_cfg, source, assembler)
        self.tau_gate = np.zeros((), np.float64)   # EMA of store-τ
        self._obs = np.zeros((), np.int64)         # observe() count
        self._cov_global = 0.0                     # gate-cadence coverage
        self._gate_dirty = False                   # refresh due at next plan
        self.k_local = self.b // self.n_hosts
        if self.impl == "sharded" and source.n <= self.b:
            raise ValueError(f"history[sharded] needs n > batch "
                             f"({source.n} <= {self.b}): the WOR sample + "
                             f"HT threshold need b+1 distinct examples")

    @property
    def active(self) -> bool:
        # the gate reads the GLOBAL coverage refreshed at the same cadence
        # as τ (observe), never a live per-host value: on uneven shards a
        # live read would flip the gate at different steps on different
        # hosts and fork the plans. Single-host the cached value equals
        # store.coverage() at the last gate refresh.
        return (self._cov_global >= self.cfg.min_coverage
                and float(self.tau_gate) > self.cfg.resolved_tau_th())

    def _maybe_refresh_gate(self):
        """The τ/coverage gate refresh is a PLAN-TIME collective: observe
        only marks it due. Planning is the point where every host has
        merged the same feedback (the gather is a sync point), so the
        gate flips on the same step everywhere; refreshing inside
        observe would gather while peers are still mid-merge. Returns
        the refreshed distribution so the same gather serves this
        step's sample (never two O(n) collectives in one plan)."""
        if not self._gate_dirty:
            return None
        self._gate_dirty = False
        # no extra smoothing: the store's per-example EMA already damps
        # minibatch noise, the gate just reads the current dataset-level τ
        sg = self.store.global_scores(self.gather_fn, use_cache=True)
        p = self.store.distribution_from(sg, self.cfg.smoothing,
                                         self.cfg.temperature)
        self.tau_gate = np.asarray(self.store.tau_from(p), np.float64)
        self._cov_global = float((sg >= 0).mean())
        return p

    def _warmup_plan(self, pstate: PipelineState, step: int):
        # warm-up: uniform sequential plan, unit weights; scores fill
        # the store
        gids = self.source.global_indices(pstate, self.b)
        plan = BatchPlan(step=step, epoch=pstate.epoch, gids=gids,
                         weights=np.ones((self.b,), np.float32))
        return plan, pstate.advance(self.b, self.source.n)

    def _plan_sharded(self, pstate: PipelineState, step: int):
        """O(b) selection: the gate, normalizer and sample all derive
        from this plan's O(1) stats allreduce + O(b·H) candidate
        exchange — never the O(n) gather. The stats are reduced at EVERY
        plan (they are the smoothing normalizer the keys need fresh), so
        the τ/coverage gate rides along at plan cadence for free."""
        dist = selection.GlobalDist(self._reduce_stats(self.cfg.temperature),
                                    n=self.store.n,
                                    smoothing=self.cfg.smoothing,
                                    temperature=self.cfg.temperature)
        self.tau_gate = np.asarray(dist.tau(), np.float64)
        self._cov_global = dist.coverage
        if not self.active:
            return self._warmup_plan(pstate, step)
        gids, probs, w, _ = selection.sample_sharded(
            self.store, dist, self.b, seed=self.seed, salt=self.SALT,
            step=step, exchange=self.topk_fn, n_hosts=self.n_hosts)
        plan = BatchPlan(step=step, epoch=pstate.epoch, gids=gids,
                         probs=probs, weights=w,
                         is_flag=max(float(self.tau_gate), 1.0))
        return plan, pstate.advance(self.b, self.source.n)

    def plan(self, pstate: PipelineState, step: int):
        if self.impl == "sharded":
            return self._plan_sharded(pstate, step)
        p = self._maybe_refresh_gate()
        if not self.active:
            return self._warmup_plan(pstate, step)
        if p is None:
            p = self.store.global_distribution(self.cfg.smoothing,
                                               self.cfg.temperature,
                                               gather_fn=self.gather_fn,
                                               use_cache=True)
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, self.SALT, int(step)]))
        gids = rng.choice(self.store.n, size=self.b, replace=True,
                          p=p).astype(np.int64)
        # unbiased for the global mean: wᵢ = 1/(n·pᵢ), E_p[w·x] = x̄
        w = (1.0 / (self.store.n * p[gids])).astype(np.float32)
        # is_flag carries the live store-τ (≥1) for the optional lr boost
        plan = BatchPlan(step=step, epoch=pstate.epoch, gids=gids,
                         probs=p[gids], weights=w,
                         is_flag=max(float(self.tau_gate), 1.0))
        return plan, pstate.advance(self.b, self.source.n)

    def observe(self, plan, scores) -> None:
        super().observe(plan, scores)
        self._obs = self._obs + 1
        # τ over the store is O(n) host work (plus the strided gather when
        # multi-host) — refresh the gate periodically, not every step
        n_obs = int(self._obs)
        if n_obs == 1 or n_obs % max(self.cfg.gate_every, 1) == 0:
            self._gate_dirty = True

    def stats(self) -> dict:
        return {"store_coverage": self.store.coverage(),
                "store_tau": float(self.tau_gate),
                "sampler_active": float(self.active)}

    def state_dict(self) -> dict:
        d = super().state_dict()
        d["tau_gate"] = self.tau_gate
        d["obs"] = self._obs
        d["cov_global"] = np.asarray(self._cov_global, np.float64)
        # a refresh marked due but not yet run must survive resume, or the
        # restored run's gate flips one cycle later than the original
        d["gate_dirty"] = np.asarray(self._gate_dirty, np.uint8)
        return d

    def load_state_dict(self, d) -> None:
        super().load_state_dict(d)
        self.tau_gate = np.asarray(d["tau_gate"], np.float64).reshape(())
        self._obs = np.asarray(d.get("obs", 0), np.int64).reshape(())
        self._cov_global = float(np.asarray(d.get("cov_global", 0.0)))
        self._gate_dirty = bool(np.asarray(d.get("gate_dirty", 0)))


class SelectiveSampler(Sampler):
    """Top-k selective backprop over a sliding candidate window, ranked by
    the score memory instead of a fresh scoring pass (the memory is what
    makes this cheaper than the original Biggest-Losers forward). The
    window is ranked by the GLOBAL score vector, so every host trains on
    its shard of the one global top-b — not a per-host top-k_local.

    On the ``"sharded"`` impl each host ranks only the window rows it
    owns and exchanges b candidates (pool position + priority) — the
    merged global top-b is BITWISE identical to ranking the gathered
    vector (priorities are raw stored floats, ties broken by pool
    position on both paths), with O(W/H + b·H) cost instead of O(n)."""

    scheme = "selective"
    plan_is_pure = False     # plans read the (mutable) score memory

    def __init__(self, run_cfg, source, assembler=None):
        super().__init__(run_cfg, source, assembler)
        self.k_local = self.b // self.n_hosts
        self.window = (self.cfg.selective_window
                       or self.b * self.icfg.presample_ratio)
        # clamp to the dataset: a window past n would wrap duplicate ids
        # into one pool and roll multiple epochs (= staleness decays) per
        # step on tiny datasets
        self.window = min(self.window, source.n)
        if self.window < self.b:
            raise ValueError(f"selective window {self.window} < batch {self.b}")

    def plan(self, pstate: PipelineState, step: int):
        pool = self.source.global_indices(pstate, self.window)
        if self.impl == "sharded":
            def block(store):
                return selection.local_rank_candidates(pool, store, self.b)
            if self.topk_fn is not None:        # simulated multi-host
                cand = self.topk_fn(block, k_each=self.b,
                                    n_hosts=self.n_hosts)
            else:
                from repro.distributed.collectives import exchange_topk
                cand = exchange_topk(block(self.store), k_each=self.b,
                                     n_hosts=self.n_hosts)
            order = selection.merge_rank(cand, self.b)
        else:
            sg = self.store.global_scores(self.gather_fn, use_cache=True)
            pri = sg[pool].astype(np.float64)
            # never-seen ids rank highest (optimistic init: visit everything)
            pri = np.where(pri >= 0, pri, np.inf)
            # stable partial sort: ties (e.g. all-unseen cold start) keep
            # pool order, so the ranking is deterministic on every host
            order = np.argsort(-pri, kind="stable")[:self.b]
        plan = BatchPlan(step=step, epoch=pstate.epoch, gids=pool[order],
                         is_flag=1.0)
        return plan, pstate.advance(self.window, self.source.n)


SCHEMES = {c.scheme: c for c in
           (UniformSampler, PresampleSampler, HostPresampleSampler,
            FusedPresampleSampler, HistorySampler, SelectiveSampler)}


def make_sampler(run_cfg, source, assembler=None) -> Sampler:
    if run_cfg.imp.selection_impl not in ("auto", "gather", "sharded"):
        raise ValueError(
            f"unknown imp.selection_impl {run_cfg.imp.selection_impl!r}; "
            f"have ('auto', 'gather', 'sharded')")
    pimpl = run_cfg.imp.presample_impl
    if pimpl not in ("auto", "step", "host", "fused"):
        raise ValueError(
            f"unknown imp.presample_impl {pimpl!r}; "
            f"have ('auto', 'step', 'host', 'fused')")
    if getattr(run_cfg.imp, "score_prune", "off") not in ("off",
                                                          "conservative"):
        raise ValueError(
            f"unknown imp.score_prune {run_cfg.imp.score_prune!r}; "
            f"have ('off', 'conservative')")
    scheme = run_cfg.sampler.scheme
    if scheme == "presample":
        # presample execution routing: "auto" keeps the legacy behaviour
        # (the host_score flag picks the engine-backed host path over the
        # in-step device path); "host"/"fused"/"step" force theirs
        if pimpl == "auto":
            pimpl = "host" if run_cfg.sampler.host_score else "step"
        scheme = {"step": "presample", "host": "presample_host",
                  "fused": "presample_fused"}[pimpl]
    if scheme not in SCHEMES:
        raise ValueError(f"unknown sampler scheme {scheme!r}; "
                         f"have {sorted(SCHEMES)}")
    if not run_cfg.imp.enabled and scheme in ("history", "selective",
                                              "presample_host",
                                              "presample_fused"):
        # imp.enabled=False is the global IS kill-switch; score-memory /
        # host-side selection IS importance sampling, so fall back to
        # uniform (on-device presample handles the switch itself via its
        # τ gate="never")
        scheme = "uniform"
    return SCHEMES[scheme](run_cfg, source, assembler)
