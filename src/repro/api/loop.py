"""The event-hook training loop.

``TrainLoop`` is the old ``Trainer.fit`` monolith decomposed: the loop
keeps only the operations whose ORDER defines training semantics — the
two-phase sampler handshake, step dispatch, double-buffered scoring,
deferred score feedback, and bounded straggler retries — and everything
else (logging, metrics history, checkpointing, straggler escalation)
observes it through events:

    loop_start(start, steps)
    step_start(step, batch, meta)         after the batch materialises
    step_timed(step, attempt, dt)         every attempt; hooks VOTE retry
    retry(step, attempt, dt)              a vote passed; same batch re-runs
    step_end(step, metrics)               accepted step, metrics enriched
    scores_ready(step, meta, scores)      feedback drained into the store
    checkpoint(step, payload)             a checkpoint was written
    loop_end(state, history)

Hooks are composable observers (``repro.api.hooks``); ``step_timed`` is
the one control-point — any hook returning True requests a retry of the
same batch (bounded by ``run.max_step_retries``), which is how straggler
escalation plugs in without owning the loop. The operational order is a
step-for-step transplant of the pre-hook loop, so metrics (loss/τ
sequences) are bit-identical to it (``tests/test_api_loop.py`` pins this
against a hand-rolled reference loop).

Hot-path overlap (``imp.overlap_scoring``): batch k+1's engine scoring is
dispatched (against pre-update params) just ahead of batch k's update —
ahead, because the update may donate those params — and batch k-1's
score feedback (device→host transfer + ScoreStore merges) runs on the
host behind the device work. No synchronous ``device_get`` sits on the
dispatch critical path.

Data flows through the SELECTION PLANE: the loop consumes ``BatchPlan``s
(``repro.data.plan``) from a per-run ``DataPlane`` rather than raw
batches from the sampler. For pure-plan schemes the plane pre-plans,
pre-gathers, and pre-transfers up to ``run.data.prefetch_depth`` batches
on worker threads (overlapping both the update and any in-flight engine
scoring); store/engine-coupled schemes pass through the sampler's own
two-phase ``begin``/``finish``. The event payload formerly called
``meta`` IS the step's plan (plans keep dict-style ``meta["gids"]`` /
``meta["is_flag"]`` access for old hooks). The checkpointed pipeline
cursor doubles as the plan cursor — resume re-plans bitwise.
"""
from __future__ import annotations

import sys
import time

import jax
import numpy as np

from repro import obs
from repro.distributed import collectives
from repro.runtime import faults
from repro.runtime.membership import MembershipChange

EVENTS = ("loop_start", "step_start", "step_timed", "retry", "step_end",
          "scores_ready", "checkpoint", "membership_change", "loop_end")


class TrainLoop:
    """Runs one training loop over an ``Experiment``'s composition.

    The loop reads the experiment's parts (``step_fn``, ``sampler``,
    ``monitor``, ``ckpt``) through the experiment at call time, so tests
    and benchmarks that swap them (fake monitors, recording step fns)
    keep working.
    """

    def __init__(self, experiment, hooks=()):
        self.exp = experiment
        self.hooks = list(hooks)
        self.state = None            # live train state (post last dispatch)
        self.pstate = None           # live pipeline state (= plan cursor)
        self.plane = None            # per-run DataPlane (made in run())
        self.steps_target = 0
        self.steps_run = 0
        self._pending = None         # (step, plan, device scores) to observe
        self._failed_hooks = set()   # hook classes already reported once
        # telemetry (inert unless run.obs enables the registry)
        self._sp_dispatch = obs.span("loop.dispatch")
        self._sp_drain = obs.span("loop.drain_feedback")
        self._sp_sync = obs.span("loop.sync")
        self._sp_emit = {e: obs.span(f"loop.emit.{e}") for e in EVENTS}
        self._h_step = obs.histogram("loop.step_s")
        self._c_steps = obs.counter("loop.steps")
        self._c_retries = obs.counter("loop.retries")
        self._c_hook_errors = obs.counter("loop.hook_errors")
        self._c_h2d = obs.counter("loop.h2d_bytes")

    # -- events ---------------------------------------------------------------
    def emit(self, event, *args) -> None:
        """Dispatch an event to every hook, ISOLATED: hooks are
        observers, so a raising hook must not kill the training run —
        the failure is counted (``loop.hook_errors``) and reported once
        per hook class. The one exception is ``step_timed``
        (``_vote_retry``): its return value is loop SEMANTICS (retry
        votes), so it stays un-guarded by design. The hooks' host time is
        the span ``loop.emit.<event>``."""
        with self._sp_emit[event]:
            for h in self.hooks:
                try:
                    getattr(h, "on_" + event)(self, *args)
                except Exception as e:
                    self._c_hook_errors.inc()
                    cls = type(h)
                    if cls not in self._failed_hooks:
                        self._failed_hooks.add(cls)
                        print(f"[repro] hook {cls.__name__}.on_{event} "
                              f"raised {type(e).__name__}: {e} — hook "
                              f"errors are isolated; reporting this hook "
                              f"class once", file=sys.stderr, flush=True)

    def _vote_retry(self, step, attempt, dt) -> bool:
        # list, not generator: every hook observes every attempt.
        # Deliberately NOT exception-isolated — retry votes are control
        # flow, not observation (see emit()).
        with self._sp_emit["step_timed"]:
            local = any([h.on_step_timed(self, step, attempt, dt)
                         for h in self.hooks])
        # The local vote is derived from this host's wall-clock
        # (StragglerHook), so acting on it alone would re-dispatch the
        # jitted step — and its collectives — on this host only: the
        # lockstep deadlock. OR-reduce so every host takes the same
        # branch (identity single-process AND after a solo reshard —
        # which is why n_hosts is the sampler's CURRENT membership, not
        # the launch-time process count).
        return collectives.allreduce_any(
            local, n_hosts=self.exp.sampler.n_hosts)

    # -- score feedback (deferred, off the dispatch critical path) ------------
    def drain_feedback(self) -> None:
        """Flush the previous step's score feedback into the ScoreStore.

        Called right AFTER the next step (and its overlapped scoring) has
        been dispatched: the scores were materialised when that previous
        step completed, so the transfer is a copy, and the store's host
        work (EMA merges, periodic O(n) τ-gate refresh) overlaps the
        device work now in flight instead of stalling the loop.
        """
        if self._pending is not None:
            step, plan, scores = self._pending
            self._pending = None
            with self._sp_drain:
                scores = np.asarray(jax.device_get(scores))
                self.exp.sampler.observe(plan, scores)
            self.emit("scores_ready", step, plan, scores)

    # -- checkpointing (invoked by CheckpointHook) ----------------------------
    def save_checkpoint(self, step: int, final: bool = False) -> None:
        """Snapshot {train, sampler} plus the serialized run config (so the
        run is reproducible from the checkpoint alone) and the pipeline
        cursor. Drains feedback first — the payload must see the store."""
        exp = self.exp
        if exp.ckpt is None:
            return
        from repro.api.config import to_dict
        self.drain_feedback()
        payload = exp.checkpoint_payload(self.state)
        exp.ckpt.save_async(step, payload,
                            meta={"pipeline": self.pstate.as_dict(),
                                  "run_config": to_dict(exp.run),
                                  "source": exp.source_spec})
        self.emit("checkpoint", step, payload)
        if final:
            exp.ckpt.wait()

    # -- the loop -------------------------------------------------------------
    def run(self, steps=None):
        exp = self.exp
        run = exp.run
        steps = steps or run.steps
        state, pstate, start = exp.resume_or_init()
        self.state, self.pstate = state, pstate
        self.steps_target, self.steps_run = steps, 0
        self._pending = None
        from repro.api.hooks import MetricsHistoryHook
        hist_hook = next((h for h in self.hooks
                          if isinstance(h, MetricsHistoryHook)), None)
        history = hist_hook.history if hist_hook is not None else []
        self.emit("loop_start", start, steps)
        if start >= steps:
            # resume-at-final-step: nothing to train. Crucially do NOT
            # begin() — the old loop leaked an in-flight handle (and its
            # engine scoring dispatch) here — and do not rewrite the
            # checkpoint the completed run already committed.
            self.emit("loop_end", state, history)
            return state, history
        overlap = run.imp.overlap_scoring
        plane = self.plane = exp.make_plane()
        try:
            return self._run_steps(plane, state, pstate, start, steps,
                                   overlap, history)
        finally:
            # also on exceptions (step failures, surfaced gather errors):
            # worker threads must not outlive the run
            plane.stop()

    def _handle_membership(self, exc, plane, step):
        """A collective deadline, an injected fault, or straggler
        escalation surfaced as a ``MembershipChange`` at ``step``. Stop
        the (possibly wedged) plane, let the experiment resolve the
        survivor set and reshard onto it, and hand back a fresh plane —
        the caller re-``begin``s at the SAME plan cursor, so the
        interrupted step replays under the new membership (bitwise the
        plan a cold start at this cursor + membership would produce)."""
        import dataclasses
        event = dataclasses.replace(exc.event, step=step)
        plane.stop()
        event, stats = self.exp.on_membership_change(event)
        self.emit("membership_change", step, event, stats)
        plane = self.plane = self.exp.make_plane()
        return plane

    def _finish_with_retry(self, plane, handle, state):
        """Pop the step's batch. On a pipelined (pop-again) plane a
        surfaced gather error is transient by contract — the worker has
        already re-queued the plan, so the retried batch is right behind
        the error — re-pop within the retry budget. Fatal plan errors,
        passthrough/finalize planes (whose handles are consumed by
        ``finish``), and membership changes propagate untouched."""
        retriable = getattr(plane, "pipelined", False) \
            and not getattr(plane, "finalize", False)
        budget = self.exp.run.max_step_retries
        for attempt in range(budget + 1):
            try:
                return plane.finish(handle, params=state["params"])
            except MembershipChange:
                raise
            except Exception:
                if not retriable or attempt == budget \
                        or getattr(plane, "fatal", None) is not None:
                    raise
                self._c_retries.inc()

    def _run_steps(self, plane, state, pstate, start, steps, overlap,
                   history):
        exp = self.exp
        run = exp.run
        handle = plane.begin(
            pstate, start, params=state["params"] if overlap else None)
        i = start
        while i < steps:
            # one step marker per iteration: the profiler's step, and the
            # thread the benchmark reads as the loop's
            with jax.profiler.StepTraceAnnotation("train", step_num=i):
                faults.set_step(i)
                faults.die_if(i)
                step_state0, resume_at = state, i
                try:
                    batch, plan, pstate_next = self._finish_with_retry(
                        plane, handle, state)
                    # the train path's H2D: fused presample hands device arrays
                    # through (asarray is a no-op) and the counter stays at
                    # zero — the per-step transfer claim the fused benchmark
                    # checks
                    h2d = sum(np.asarray(v).nbytes for v in batch.values()
                              if not isinstance(v, jax.Array))
                    if h2d:
                        self._c_h2d.inc(h2d)
                    batch = {k: jax.numpy.asarray(v) for k, v in batch.items()}
                    self.emit("step_start", i, batch, plan)
                    launched_next = False
                    dt_total = 0.0
                    for attempt in range(run.max_step_retries + 1):
                        t0 = time.time()
                        prev_state = state
                        if not launched_next and i + 1 < steps:
                            # double-buffer: batch k+1's scoring against the
                            # PRE-update params, dispatched before batch k's
                            # update, which may donate them (scores one step
                            # stale — selection tolerates that)
                            handle = plane.begin(
                                pstate_next, i + 1,
                                params=state["params"] if overlap else None)
                            launched_next = True
                        with self._sp_dispatch:
                            if exp.step_is_flagged:
                                state, metrics = exp.step_fn(
                                    state, batch,
                                    jax.numpy.asarray(plan["is_flag"],
                                                      jax.numpy.float32))
                            else:
                                state, metrics = exp.step_fn(state, batch)
                        if exp.donates_state:
                            # the update consumed its input state, so there
                            # is nothing to replay step i from: a membership
                            # change from here on keeps this update
                            # (unrecorded) and resumes at step i + 1
                            step_state0, resume_at = state, i + 1
                        self.state = state
                        # previous step's score feedback overlaps device work
                        self.drain_feedback()
                        # the host blocks here until the update is done, then
                        # reads each scalar
                        with self._sp_sync:
                            scores = metrics.pop("sample_scores", None)
                            metrics = {k: float(v)
                                       for k, v in metrics.items()}
                        dt = time.time() - t0 + faults.slow_penalty(i)
                        dt_total += dt
                        if not self._vote_retry(i, attempt, dt) \
                                or attempt == run.max_step_retries:
                            # accepted — or retries exhausted, in which
                            # case the (already computed, merely slow)
                            # update is kept: the batch is RETRIED under a
                            # skip and never dropped
                            break
                        # straggler escalation: drop this attempt's
                        # result (params AND score feedback) and RETRY THE
                        # SAME BATCH — bounded by max_step_retries; the
                        # monitor's own skip budget forces a sync once
                        # exhausted
                        state = prev_state
                        self.state = state
                        self._c_retries.inc()
                        self.emit("retry", i, attempt, dt)
                except MembershipChange as mc:
                    # membership is a loop event, not a crash: drop this
                    # step's partial work (params AND the previous step's
                    # undrained feedback — its row slicing belonged to the
                    # old membership), reshard, and replay step i from the
                    # same plan cursor under the survivors.
                    state = step_state0
                    self.state = state
                    self._pending = None
                    if resume_at > i:
                        i, pstate = resume_at, pstate_next
                        self.pstate = pstate
                    plane = self._handle_membership(mc, plane, i)
                    if i < steps:
                        handle = plane.begin(
                            pstate, i,
                            params=state["params"] if overlap else None)
                    continue
                if scores is not None:
                    # close the loop lazily: scores flow into the score
                    # memory behind the NEXT step's device work
                    # (drain_feedback)
                    self._pending = (i, plan, scores)
                pstate = pstate_next
                self.pstate = pstate
                # retried steps used to mis-report timing: `dt` is the LAST
                # attempt only. Carry the attempt count and the cumulative
                # wall time so consumers can tell a clean 50 ms step from a
                # 3-attempt 150 ms one.
                metrics.update(step=i, dt=dt, attempts=attempt + 1,
                               dt_total=dt_total, **exp.sampler.stats())
                self._h_step.observe(dt)
                self._c_steps.inc()
                self.steps_run += 1
                self.emit("step_end", i, metrics)
                i += 1
        plane.stop()
        self.drain_feedback()
        self.emit("loop_end", state, history)
        return state, history
