"""The obs metric-name schema — the ONE source of truth.

Every instrument name the codebase records (``obs.counter`` / ``gauge``
/ ``histogram`` / ``span``) and every record-level family the telemetry
plane emits is declared here, exactly once. Three consumers keep it
honest, so the old three-way drift (code vs README table vs CI check)
is structurally impossible:

* ``tests/obs_schema_check.py`` validates every metric name in the
  JSONL a real run emits against this table;
* the README's Observability table is GENERATED from it
  (``python -m repro.obs.schema`` prints the markdown;
  ``tests/test_repro_lint.py`` asserts the README block matches);
* ``tools/repro_lint`` rule RL005 cross-checks, statically, that every
  metric-name literal in ``src/`` matches an entry here and that every
  non-record entry is recorded somewhere in the code.

Names are dotted; a ``*`` segment marks a dynamic family (the code
builds the name with an f-string — e.g. one ``calls``/``bytes`` counter
pair per collective). ``kind="record"`` entries are not registry
instruments: they name families injected into the flushed JSONL record
by the ``TelemetryHook`` (RL005 skips them).

This module is intentionally dependency-free and ``ast.literal_eval``
-friendly: the linter reads ``SCHEMA`` without importing (no jax, no
numpy), so the static gate runs on a bare Python.
"""
from __future__ import annotations

# (name, kind, description) — kinds: counter | gauge | histogram | span
# | record. Keep alphabetical-by-prefix; the README table preserves this
# order.
SCHEMA = (
    ("collectives.*.bytes", "counter",
     "local payload bytes per collective (entry-counted, so 1-process "
     "runs still show the selection-plane traffic shape)"),
    ("collectives.*.calls", "counter",
     "calls per collective (`gather_host_scores`, `allgather_rows`, "
     "`exchange_rows`, `allreduce_stats`, `exchange_topk`, "
     "`allreduce_any`)"),
    ("collectives.*.timeouts", "counter",
     "collective attempts that breached the runtime deadline envelope "
     "(each is retried with bounded backoff; exhaustion escalates to a "
     "MembershipChange instead of hanging the pod)"),
    ("collectives.exchange_topk.k_each", "histogram",
     "candidate-block rows per exchange — the knob trading exchange "
     "bandwidth (k_each*H rows) against selection fidelity"),
    ("engine.dispatch", "span",
     "score-pass dispatch cost (host-side tracing/transfer only — the "
     "pass itself is async)"),
    ("engine.dispatches", "counter", "score passes launched"),
    ("engine.h2d_bytes", "counter",
     "bytes actually crossing host->device on the scoring path "
     "(already-device arrays are free — the fused path's claim)"),
    ("engine.jit_compiles", "counter",
     "new batch structures compiled; growth mid-run means shape churn "
     "on the scoring path"),
    ("engine.row_gathers", "counter",
     "on-device winner gathers out of a device-resident pool"),
    ("engine.take_rows", "span", "on-device row-gather dispatch"),
    ("faults.*", "counter",
     "injected faults fired, one counter per kind (`timeout` / `gather` "
     "/ `die` / `slow` — the deterministic chaos schedule of "
     "RunConfig.runtime.faults)"),
    ("health.ess", "gauge",
     "Kish effective sample size of the step's unbiasedness weights"),
    ("health.ess_frac", "gauge", "ESS / batch size"),
    ("health.gate_flips", "counter", "tau-gate open/close transitions"),
    ("health.is_active", "gauge", "1 while importance sampling is on"),
    ("health.max_weight", "gauge", "largest unbiasedness weight"),
    ("health.speedup_est", "gauge",
     "sec. 3.3 speedup estimate 3*tau*b/(B+3b); > 1 iff the paper's "
     "guaranteed-speedup condition holds (B = 0 for store-backed "
     "schemes)"),
    ("health.tau", "gauge", "live tau of the selection distribution"),
    ("health.tau_margin", "gauge", "tau - tau_th"),
    ("health.variance_gain", "gauge", "sec. 3.3 variance gain 1 - 1/tau^2"),
    ("kernels.prune.blocks_skipped", "counter",
     "whole (block_b, block_t) scoring tiles skipped because every row "
     "in the block had already lost the race (imp.score_prune)"),
    ("kernels.prune.flops_saved", "counter",
     "estimated flops the skipped tiles would have cost (~12 per "
     "logits element over each skipped row-block x time-block x vocab "
     "slab)"),
    ("kernels.prune.rows_killed", "counter",
     "pool rows whose race-key lower bound E_i/s_hat exceeded the "
     "(k+1)-th key upper bound mid-scoring — conservatively pruned"),
    ("kernels.prune.tiles_total", "counter",
     "total (block_b, block_t) scoring tiles the pruned pass planned "
     "(blocks_skipped / tiles_total = the measured skip fraction)"),
    ("loop.dispatch", "span", "step dispatch (device work is async)"),
    ("loop.drain_feedback", "span",
     "score feedback D2H + ScoreStore merge, off the dispatch path"),
    ("loop.emit.*", "span",
     "observer hooks' host time, one span per loop event (`step_end`, "
     "`step_start`, ...; `step_timed` covers the retry vote)"),
    ("loop.h2d_bytes", "counter",
     "train-batch bytes uploaded by the loop (0 on the fused presample "
     "path — its batches arrive device-resident)"),
    ("loop.hook_errors", "counter",
     "exceptions raised by observer hooks (isolated, counted)"),
    ("loop.retries", "counter", "straggler retry attempts"),
    ("loop.step_s", "histogram", "accepted-step wall time"),
    ("loop.steps", "counter", "accepted steps"),
    ("loop.sync", "span",
     "the host blocks on the step's update, then reads each step metric "
     "device->host"),
    ("plane.batches", "counter", "batches produced by the data plane"),
    ("plane.credit_stalls", "counter",
     "worker stalls waiting for queue credit"),
    ("plane.device_put_bytes", "counter",
     "bytes the plane's device-put stage uploaded"),
    ("plane.device_put_skipped", "counter",
     "batches skipping device_put because they were already "
     "device-resident (the fused finalize path)"),
    ("plane.device_put", "span", "device-put worker stage"),
    ("plane.gather", "span", "row-materialise worker stage"),
    ("plane.next_wait", "span", "consumer wait for the next batch"),
    ("plane.plan", "span", "plan worker stage"),
    ("plane.queue_depth", "gauge", "ready batches queued"),
    ("runtime.membership.events", "counter",
     "membership transitions handled (host leave/join/timeout "
     "escalations — each one reshards the ScoreStore and resumes from "
     "the plan cursor)"),
    ("runtime.membership.lost_ids", "counter",
     "score entries owned by departed hosts at reshard (they fall back "
     "to the unseen prior; the tau-gate/coverage check decides whether "
     "IS stays on)"),
    ("runtime.membership.migrated_ids", "counter",
     "surviving score entries re-homed onto the new ownership at "
     "reshard"),
    ("runtime.membership.n_hosts", "gauge",
     "current membership size after the last transition"),
    ("sampler.d2h_bytes", "counter",
     "score bytes pulled device->host (the ONE pool-sized transfer "
     "either presample path makes)"),
    ("sampler.pull_scores", "span",
     "presample score vector device->host (blocks on the score pass) "
     "plus the prune stats"),
    ("sampler.select", "span",
     "host selection from the pulled scores: the race, the ScoreStore "
     "update and the tau EMA"),
    ("sampler.selection_impl.*", "counter",
     "resolved selection impl, recorded once per run (`gather` / "
     "`sharded` — how `auto` resolved)"),
    ("step.*", "record",
     "the accepted step's metrics dict (loss, dt, attempts, dt_total, "
     "tau, ...) as flushed into each JSONL record by the TelemetryHook"),
    ("store.gather_cache.hits", "counter",
     "global-score reads served by the write-version cache"),
    ("store.gather_cache.misses", "counter",
     "global-score reads that re-gathered"),
    ("store.invalidations", "counter",
     "cache invalidations (every update/decay/restore version bump)"),
    ("store.staleness", "histogram",
     "update ticks since each revisited id was last rescored"),
    ("straggler.b_scale", "gauge",
     "current straggler batch-shrink factor (1.0 = healthy)"),
    ("straggler.deadline_s", "gauge",
     "current per-step deadline (factor x step-time EMA)"),
    ("straggler.ema_s", "gauge", "step wall-time EMA the deadline tracks"),
    ("straggler.skips", "counter",
     "steps skipped (and retried) after a deadline breach"),
)

KINDS = ("counter", "gauge", "histogram", "span", "record")


def entries():
    """The schema rows as (name, kind, description) tuples."""
    return SCHEMA


def names():
    return tuple(e[0] for e in SCHEMA)


def _pattern_matches(pattern: str, name: str) -> bool:
    """``*`` matches one or more characters (dynamic name families)."""
    import re
    rx = "".join(".+" if c == "*" else re.escape(c) for c in pattern)
    return re.fullmatch(rx, name) is not None


def match(name: str):
    """The schema entry covering ``name`` (exact first, then dynamic
    families), or None."""
    for e in SCHEMA:
        if e[0] == name:
            return e
    for e in SCHEMA:
        if "*" in e[0] and _pattern_matches(e[0], name):
            return e
    return None


def to_markdown() -> str:
    """The README Observability table, generated (one row per entry)."""
    lines = ["| name | kind | what |", "|---|---|---|"]
    for name, kind, desc in SCHEMA:
        lines.append(f"| `{name}` | {kind} | {desc} |")
    return "\n".join(lines)


if __name__ == "__main__":
    print(to_markdown())
