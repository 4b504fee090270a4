"""Mesh-context-aware sharding constraints + compressed collectives.

``constrain(x, ...)`` is a no-op outside a mesh context (CPU unit tests),
and inside one it drops axes that are absent or don't divide the dim — so
model code can state INTENT ("batch over dp, heads over model") once and
run anywhere. The special axis name ``"dp"`` expands to ("pod", "data").
"""
from __future__ import annotations

import itertools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.runtime import faults


def _note_collective(name: str, payload) -> None:
    """Count a collective call and its LOCAL payload bytes (what this host
    contributes). Called at function ENTRY — before the single-process
    identity early-returns — so the counters describe selection-plane
    traffic shape even in 1-process runs (CI smokes, examples)."""
    if not obs.enabled():
        return
    obs.counter(f"collectives.{name}.calls").inc()
    tree = payload if isinstance(payload, dict) else {"x": payload}
    obs.counter(f"collectives.{name}.bytes").inc(
        int(sum(np.asarray(v).nbytes for v in tree.values())))


def _mesh_axes():
    """Axis sizes of the mesh in context (``jax.set_mesh``), or None."""
    m = jax.sharding.get_abstract_mesh()
    if m.empty:
        return None
    return dict(zip(m.axis_names, m.axis_sizes))


def constrain(x, *entries):
    """with_sharding_constraint with axis filtering.

    entries: one per dim — None, an axis name, "dp" (pod+data), or a tuple.
    """
    axes = _mesh_axes()
    if not axes:
        return x
    spec = []
    for e, dim in zip(entries, x.shape):
        if e is None:
            spec.append(None)
            continue
        names = []
        for n in (e if isinstance(e, tuple) else (e,)):
            if n == "dp":
                names += [a for a in ("pod", "data") if a in axes]
            elif n in axes:
                names.append(n)
        size = int(np.prod([axes[n] for n in names])) if names else 1
        if names and dim % size == 0 and dim >= size:
            spec.append(tuple(names) if len(names) > 1 else names[0])
        else:
            spec.append(None)
    spec += [None] * (x.ndim - len(spec))
    return jax.lax.with_sharding_constraint(x, P(*spec))


def constrain_bsd(x, head_dim_index=None):
    """Constraint for (batch, seq, ...) activations: batch over dp when
    divisible, otherwise seq over dp (long-context decode, batch=1).
    ``head_dim_index`` optionally shards a heads dim over "model"."""
    axes = _mesh_axes()
    if not axes:
        return x
    dpn = int(np.prod([axes.get(a, 1) for a in ("pod", "data")]))
    entries = [None] * x.ndim
    if x.shape[0] % dpn == 0 and x.shape[0] >= dpn:
        entries[0] = "dp"
    elif x.ndim > 1 and x.shape[1] % dpn == 0 and x.shape[1] >= dpn:
        entries[1] = "dp"
    if head_dim_index is not None:
        entries[head_dim_index] = "model"
    return constrain(x, *entries)


# ---------------------------------------------------------------------------
# strided-shard math (ScoreStore's `id % H` ownership), pad + trim
# ---------------------------------------------------------------------------
def _require_multiprocess(name, n_hosts):
    """Multi-host collectives need one JAX process per host; a simulated
    multi-host run (tests) must inject an in-process merge instead of
    silently gathering only its own shard."""
    if jax.process_count() == 1:
        raise RuntimeError(
            f"{name}: sharded over {n_hosts} hosts but this launch has one "
            f"process — simulated multi-host runs must inject the collective "
            f"(see tests/test_plan.py)")



# deadline envelope defaults (RunConfig.runtime; see ``configure``)
_RT = {"timeout_s": 120.0, "retries": 2,
       "backoff_base_s": 0.5, "backoff_max_s": 8.0}
_kv_seq = itertools.count()


def configure(runtime_cfg=None) -> None:
    """Install the pod's ``RunConfig.runtime`` deadline/retry envelope
    (None restores defaults). Called once by ``Experiment.__init__``;
    module-level because the collectives are free functions."""
    _RT.update(
        timeout_s=float(getattr(runtime_cfg, "collective_timeout_s", 120.0)),
        retries=int(getattr(runtime_cfg, "collective_retries", 2)),
        backoff_base_s=float(getattr(runtime_cfg, "backoff_base_s", 0.5)),
        backoff_max_s=float(getattr(runtime_cfg, "backoff_max_s", 8.0)))


def _timeout_ms() -> int:
    return max(1, int(_RT["timeout_s"] * 1000.0))


class CollectiveTimeout(RuntimeError):
    """A collective attempt exceeded its deadline (retryable)."""


def _is_timeout_error(e) -> bool:
    """Classify an exception from the cross-process funnel as a deadline
    breach (retryable) vs a real bug (re-raised). The coordination
    service surfaces breaches as XlaRuntimeError with DEADLINE_EXCEEDED /
    barrier-timeout texts; injected faults and explicit
    ``CollectiveTimeout`` count too."""
    if isinstance(e, (CollectiveTimeout, faults.FaultInjected)):
        return True
    msg = str(e).lower()
    return any(tok in msg for tok in
               ("deadline", "timed out", "timeout", "barrier",
                "unavailable", "connection reset"))


def _kv_allgather(v: np.ndarray) -> np.ndarray:
    """Fixed-shape all-gather through the jax.distributed coordination
    service (KV store + barrier). XLA's CPU backend has no multi-process
    computations, so CPU multi-process launches — the 2-process CI smoke,
    dev rigs — ride this instead of ``process_allgather``. Every process
    must issue its collectives in the same order (standard SPMD): the
    monotonic call counter is the rendezvous id. The barrier timeout is
    the deadline clock of the retry envelope above."""
    from jax._src import distributed
    client = distributed.global_state.client
    if client is None:
        raise RuntimeError("_kv_allgather: jax.distributed is not "
                           "initialized (call jax.distributed.initialize)")
    pid, n = jax.process_index(), jax.process_count()
    key = f"repro/ag{next(_kv_seq)}"
    client.key_value_set(f"{key}/{pid}", v.tobytes().hex())
    client.wait_at_barrier(f"{key}/ready", timeout_in_ms=_timeout_ms())
    shards = [np.frombuffer(
        bytes.fromhex(client.blocking_key_value_get(f"{key}/{i}",
                                                    _timeout_ms())),
        v.dtype).reshape(v.shape) for i in range(n)]
    # best-effort cleanup once everyone has read (long CPU runs would
    # otherwise grow the coordinator's store without bound)
    client.wait_at_barrier(f"{key}/done", timeout_in_ms=_timeout_ms())
    if pid == 0:
        try:
            client.key_value_delete(f"{key}/")
        except Exception:
            pass
    return np.stack(shards)


def _process_allgather(v, *, op: str = "allgather") -> np.ndarray:
    """The one cross-process all-gather all collectives ride: XLA
    ``process_allgather`` on accelerator backends, the coordination-
    service KV path on CPU (where XLA has no multi-process programs).
    Returns the (n_processes, ...) stack, identical on every process.

    This funnel carries the DEADLINE ENVELOPE: each attempt is bounded
    by ``runtime.collective_timeout_s`` (the KV barrier's timeout is the
    clock — no host clock is read here), a breached attempt is retried
    up to ``runtime.collective_retries`` times behind bounded
    exponential backoff, and a persistent breach escalates into a
    ``MembershipChange`` event instead of hanging the pod. Injected
    ``timeout`` faults enter through the same classification, so the
    chaos tests exercise exactly the production path. Non-deadline
    errors re-raise unwrapped.
    """
    v = np.asarray(v)
    retries = int(_RT["retries"])
    last = None
    for attempt in range(retries + 1):
        try:
            faults.raise_if("timeout", op=op)
            if jax.default_backend() == "cpu":
                return _kv_allgather(v)
            from jax.experimental import multihost_utils
            return np.asarray(multihost_utils.process_allgather(v))
        except Exception as e:
            if not _is_timeout_error(e):
                raise
            last = e
            obs.counter(f"collectives.{op}.timeouts").inc()
        if attempt < retries:
            time.sleep(min(_RT["backoff_base_s"] * (2.0 ** attempt),
                           _RT["backoff_max_s"]))
    # retry budget exhausted: escalate to a membership event — the
    # detecting host cannot know which peers survive, so members stays
    # unknown and the degradation ladder drops it to a solo pod
    from repro.runtime.membership import MembershipChange, MembershipEvent
    raise MembershipChange(MembershipEvent(
        kind="timeout",
        reason=f"collective {op!r} exceeded its "
               f"{_RT['timeout_s']:g}s deadline on all "
               f"{retries + 1} attempts: {last}")) from last


def strided_shard_size(n_global: int, host_id: int, n_hosts: int) -> int:
    """Slots host ``host_id`` owns under strided ownership
    ``{i : i % H == h}`` — ``ceil((n - h) / H)``, correct for ANY
    ``n % H`` (shards are uneven when ``H`` does not divide ``n``)."""
    return (int(n_global) - int(host_id) + int(n_hosts) - 1) // int(n_hosts)


def pad_shard(local, n_global: int, n_hosts: int, fill=-1.0):
    """Pad a host-local strided shard to the COMMON shard length
    ``ceil(n/H)`` so a fixed-shape all-gather can carry it; the pad value
    is the unseen sentinel and is trimmed again on reassembly."""
    local = np.asarray(local)
    per = (int(n_global) + n_hosts - 1) // n_hosts
    if local.shape[0] > per:
        raise ValueError(f"shard of {local.shape[0]} > max shard {per} "
                         f"(n={n_global}, H={n_hosts})")
    padded = np.full((per,) + local.shape[1:], fill, local.dtype)
    padded[:local.shape[0]] = local
    return padded


def interleave_shards(shards, n_global: int):
    """Inverse of strided sharding: ``out[h::H] = shards[h]`` with the
    per-host padding trimmed (``shards`` is the stacked (H, ceil(n/H), ...)
    all-gather result). Pure numpy — the single definition of the
    reassembly math, shared by the multi-process gather below, the
    simulated-host test harness, and the ScoreStore's global reads."""
    shards = np.asarray(shards)
    n_hosts = shards.shape[0]
    out = np.empty((int(n_global),) + shards.shape[2:], shards.dtype)
    for h in range(n_hosts):
        size = strided_shard_size(n_global, h, n_hosts)
        out[h::n_hosts] = shards[h][:size]
    return out


# ---------------------------------------------------------------------------
# multi-host score gather (the repro.scoring engine's host-side hook)
# ---------------------------------------------------------------------------
def gather_host_scores(local_scores, *, host_id=None, n_hosts=None,
                       n_global=None):
    """Assemble the GLOBAL score vector from host-local shards.

    The ``ScoreStore`` strides example ids over hosts (host ``h`` owns
    ``{i : i % H == h}``), so the global vector interleaves the per-host
    shards: ``out[h::H] = shard_h``. Single-process (tests, CPU examples)
    this is the identity; with multiple processes it all-gathers the
    host-local shards via ``multihost_utils`` before interleaving.
    Uneven shards (``n_global % n_hosts != 0``) are padded with the ``-1``
    sentinel to the common length and trimmed on reassembly
    (``pad_shard`` / ``interleave_shards``).
    """
    local = np.asarray(local_scores, np.float32).reshape(-1)
    _note_collective("gather_host_scores", local)
    n_hosts = jax.process_count() if n_hosts is None else int(n_hosts)
    if n_hosts == 1:
        return local if n_global is None else local[:n_global]
    if n_global is None:
        # shard lengths differ across hosts when n % n_hosts != 0, and
        # process_allgather needs one fixed shape — the caller must say
        # how long the global vector is
        raise ValueError("n_global is required for a multi-process gather "
                         "(host-local shards may be uneven)")
    host_id = jax.process_index() if host_id is None else int(host_id)
    _require_multiprocess("gather_host_scores", n_hosts)
    expect = strided_shard_size(n_global, host_id, n_hosts)
    if local.size != expect:
        raise ValueError(f"host {host_id}/{n_hosts} shard has {local.size} "
                         f"slots, expected {expect} for n={n_global}")
    # repro-lint: disable=RL002 -- deliberate fail-fast: a mis-sized shard
    # means the plan sharding itself diverged, so aborting THIS host loudly
    # beats feeding the gather garbage; peers are bounded by the KV-barrier
    # timeout rather than hanging forever
    shards = _process_allgather(pad_shard(local, n_global, n_hosts),
                                op="gather_host_scores")
    return interleave_shards(shards, n_global)


# ---------------------------------------------------------------------------
# row-plane collectives (BatchPlan assembly across hosts)
# ---------------------------------------------------------------------------
def allgather_rows(local_rows, *, n_rows: int, n_hosts=None):
    """Concatenate per-host CONTIGUOUS row blocks into the full global
    batch: host ``h`` of ``H`` contributes rows ``[h·R/H, (h+1)·R/H)``
    (a ``BatchPlan.row_slice``), the result has all ``R`` rows on every
    host. ``local_rows`` is an array or a dict of arrays sharing a leading
    row axis. Identity when single-process.
    """
    n_hosts = jax.process_count() if n_hosts is None else int(n_hosts)
    single = not isinstance(local_rows, dict)
    tree = {"x": local_rows} if single else local_rows
    _note_collective("allgather_rows", tree)
    if n_hosts == 1:
        out = {k: np.asarray(v)[:n_rows] for k, v in tree.items()}
        return out["x"] if single else out
    if int(n_rows) % n_hosts:
        raise ValueError(f"{n_rows} rows not divisible by {n_hosts} hosts")
    _require_multiprocess("allgather_rows", n_hosts)
    out = {}
    for k, v in tree.items():
        v = np.asarray(v)
        shards = _process_allgather(v, op="allgather_rows")
        out[k] = shards.reshape((-1,) + v.shape[1:])[:n_rows]
    return out["x"] if single else out


def exchange_rows(contrib, row_mask, *, lo: int, hi: int, n_hosts=None):
    """Merge per-host row CONTRIBUTIONS and return rows ``[lo, hi)``.

    Partitioned data sources can only materialise the example ids they
    hold, so each host fills the rows of the global batch it CAN produce
    (``row_mask`` True there, zeros elsewhere) and this exchange routes
    every row to the host whose data-parallel shard needs it. Implemented
    as a masked all-gather + sum (every row is produced by exactly one
    host); single-process it just slices the (complete) contribution.
    """
    n_hosts = jax.process_count() if n_hosts is None else int(n_hosts)
    row_mask = np.asarray(row_mask, bool)
    _note_collective("exchange_rows", contrib)
    if n_hosts == 1:
        if not row_mask.all():
            raise ValueError("single-process exchange with missing rows "
                             f"({int((~row_mask).sum())} unfilled)")
        return {k: np.asarray(v)[lo:hi] for k, v in contrib.items()}
    _require_multiprocess("exchange_rows", n_hosts)
    out = {}
    for k, v in contrib.items():
        v = np.where(row_mask.reshape((-1,) + (1,) * (np.asarray(v).ndim - 1)),
                     np.asarray(v), 0)
        shards = _process_allgather(v, op="exchange_rows")
        out[k] = shards.sum(axis=0)[lo:hi].astype(np.asarray(v).dtype)
    return out


def allgather_owned(values, gids, *, pad_to: int, n_global: int,
                    n_hosts=None):
    """Scatter per-host OWNED ``(gid, value)`` pairs into one global
    vector — the score-migration collective of the elastic reshard path
    (``repro.runtime.elastic``): each surviving host contributes the
    (sparse, arbitrarily-assigned) entries it held under the OLD
    ownership, every host receives the identical dense ``(n_global,)``
    vector with ``-1`` (the unseen sentinel) where no survivor owned the
    id. ``pad_to`` is the common block length (max surviving shard size,
    computed identically on every host from the old ownership), so the
    exchange rides the one fixed-shape all-gather funnel: gids and
    values pack into a single ``(2, pad_to)`` f64 block (f64 carries
    int ids exactly below 2**53). Identity-scatter single-process.
    """
    values = np.asarray(values, np.float64).reshape(-1)
    gids = np.asarray(gids, np.int64).reshape(-1)
    if values.shape != gids.shape:
        raise ValueError(f"allgather_owned: {values.size} values vs "
                         f"{gids.size} gids")
    _note_collective("allgather_owned", {"gids": gids, "values": values})
    n_hosts = jax.process_count() if n_hosts is None else int(n_hosts)
    out = np.full(int(n_global), -1.0, np.float64)
    if n_hosts == 1:
        out[gids] = values
        return out
    _require_multiprocess("allgather_owned", n_hosts)
    if values.size > int(pad_to):
        raise ValueError(f"allgather_owned: {values.size} entries exceed "
                         f"pad_to {pad_to}")
    packed = np.full((2, int(pad_to)), -1.0, np.float64)
    packed[0, :gids.size] = gids
    packed[1, :values.size] = values
    shards = _process_allgather(packed, op="allgather_owned")
    for h in range(n_hosts):
        keep = shards[h, 0] >= 0
        out[shards[h, 0, keep].astype(np.int64)] = shards[h, 1, keep]
    return out


# ---------------------------------------------------------------------------
# sharded-selection collectives: O(1) stats + O(b·H) candidate exchange
# ---------------------------------------------------------------------------
def allreduce_stats(local_stats, *, n_hosts=None):
    """Sum tiny per-shard sufficient-stat vectors across hosts — the O(1)
    collective behind the sharded selection path's τ-gate, smoothing
    normalizer and staleness-decay attractor (``repro.sampler.selection``
    owns the math). Implemented as all-gather + host-order sum so every
    host computes the bitwise-identical reduction; identity
    single-process."""
    local = np.asarray(local_stats, np.float64)
    _note_collective("allreduce_stats", local)
    n_hosts = jax.process_count() if n_hosts is None else int(n_hosts)
    if n_hosts == 1:
        return local.copy()
    _require_multiprocess("allreduce_stats", n_hosts)
    return _process_allgather(local, op="allreduce_stats").sum(axis=0)


def allreduce_any(flag, *, n_hosts=None) -> bool:
    """Global OR of one per-host boolean — the lockstep vote primitive.

    A host-local decision that re-dispatches device work (the straggler
    retry vote is THE case: it is derived from this host's wall-clock)
    must never steer control flow ahead of collectives on its own: if
    host 3 retries a step the others accepted, host 3 re-enters the
    jitted step's collectives alone and the fleet deadlocks. OR-reducing
    the votes makes the decision identical everywhere — all hosts retry,
    or none do. One bool per step; identity single-process.
    """
    local = np.asarray([bool(flag)])
    _note_collective("allreduce_any", local)
    n_hosts = jax.process_count() if n_hosts is None else int(n_hosts)
    if n_hosts == 1:
        return bool(flag)
    _require_multiprocess("allreduce_any", n_hosts)
    return bool(_process_allgather(local, op="allreduce_any").any())


def exchange_topk(candidates, *, k_each: int, n_hosts=None):
    """Exchange fixed-size per-host candidate blocks — the O(b·H)
    selection-plane collective that replaces the O(n) score gather.

    ``candidates`` is a dict of (k_each, ...) arrays (this host's padded
    local top-k block: ids/keys/probs or positions/priorities); the
    result concatenates every host's block host-major, ``(k_each·H, ...)``
    per key, identical on all hosts — the deterministic merge runs on the
    same bytes everywhere. Rides ``allgather_rows``; identity
    single-process."""
    n_hosts = jax.process_count() if n_hosts is None else int(n_hosts)
    _note_collective("exchange_topk", candidates)
    if obs.enabled():
        # candidate-block size distribution: the knob that trades exchange
        # bandwidth (k_each·H rows) against selection fidelity
        obs.histogram("collectives.exchange_topk.k_each").observe(int(k_each))
    for k, v in candidates.items():
        if np.asarray(v).shape[0] != int(k_each):
            raise ValueError(f"candidate block {k!r} has "
                             f"{np.asarray(v).shape[0]} rows != k_each "
                             f"{k_each} (blocks must be padded)")
    return allgather_rows(candidates, n_rows=int(k_each) * n_hosts,
                          n_hosts=n_hosts)


# ---------------------------------------------------------------------------
# compressed cross-pod all-reduce (used via shard_map by grad compression)
# ---------------------------------------------------------------------------
def ring_allreduce_compressed(x, axis_name, compress, decompress):
    """All-reduce over ``axis_name`` exchanging COMPRESSED payloads via
    ppermute (ring reduce). compress/decompress map f32 -> payload pytree ->
    f32. Used for the cross-pod gradient reduction where ICI/DCN bandwidth
    dominates; within-pod reductions stay full precision."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    acc = x
    payload = compress(x)
    for i in range(n - 1):
        payload = jax.tree_util.tree_map(
            lambda t: jax.lax.ppermute(
                t, axis_name,
                [(j, (j + 1) % n) for j in range(n)]),
            payload)
        acc = acc + decompress(payload)
    return acc
