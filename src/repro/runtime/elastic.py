"""Elastic membership: reshard the plan world in place, no checkpoint
round-trip.

The pre-plan-world recovery story (die → scheduler restart → restore
from the last COMMITted checkpoint) still exists at the bottom of this
module (``remesh``/``rebalance_microbatches``/``recover``), but it
throws away everything since the last checkpoint. The plan world does
better: all durable selection state is (a) the ``ScoreStore`` shards and
(b) the ``DataPlane`` plan cursor, and plans are pure functions of
(cursor, step, membership) — so a membership change only needs to

1. re-home the score shards onto the survivors (``migrate_store``:
   rendezvous/HRW ownership over stable member uids, surviving entries
   carried by ``collectives.allgather_owned``, entries owned by departed
   hosts falling back to the unseen prior — the τ-gate/coverage check
   then decides whether IS stays on: graceful degradation, never wrong
   plans);
2. point the sampler/source/assembler at the new (rank, n_hosts) view
   (``reshard_sampler``); and
3. restart the data plane at the loop's current plan cursor.

Post-reshard plans are bitwise identical to a cold start at the same
cursor with the same membership — the membership-transition tests in
``tests/test_plan.py`` pin this for every scheme × selection impl.

Degradation ladder (who calls this, with what members):

* scheduled leave/join — the fault plane or an external controller
  raises ``MembershipChange`` with the explicit survivor set;
* straggler escalation — the monitor's deadline machinery exhausts its
  batch-shrink/skip budget and escalates with the peer set minus the
  straggler (today: the full peer set, a resync);
* collective deadline exhaustion — the detecting host cannot know who
  else is alive (``event.members == ()``), so it degrades to a solo pod
  of itself and keeps training on its own data shard: worst case the
  paper's variance reduction is lost, correctness never is.
"""
from __future__ import annotations

import numpy as np

import jax

from repro import obs
from repro.runtime.membership import MembershipEvent


def member_uids(ownership) -> tuple:
    """The stable uids of an ownership's members. Strided ownership is
    the launch-time partition, where rank == uid by construction."""
    return tuple(getattr(ownership, "members",
                         range(ownership.n_hosts)))


def migrate_store(old, members, me_uid: int, *, allgather=None,
                  pad_to=None):
    """Rebuild a ``ScoreStore`` under the new membership, migrating every
    surviving entry. Returns ``(new_store, n_migrated, n_lost)``.

    Each survivor contributes its ENTIRE old shard (ids + sentinel
    values) to one ``collectives.allgather_owned`` over the new
    membership; the resulting global sentinel vector is adopted by a
    fresh rendezvous-owned store via ``update`` (write-through on a
    fresh store, so migration is exact — no EMA smearing). Ids whose old
    owner departed stay at the unseen sentinel. ``old=None`` is a
    JOINING host (no shard to contribute); it must pass ``pad_to`` (the
    max old surviving shard size, which contributors derive themselves).
    Simulated runs inject ``allgather``.
    """
    from repro.distributed.collectives import allgather_owned
    from repro.sampler.store import ScoreStore
    members = tuple(sorted(int(u) for u in members))
    if old is not None:
        gids, vals = old.my_global_ids(), old.sentinel_scores()
        n_global = old.n
        if pad_to is None:
            pad_to = int(old.shard_sizes().max())
    else:
        if pad_to is None:
            raise ValueError("a joining host has no old shard to size the "
                             "exchange from — pass pad_to explicitly")
        raise ValueError("migrate_store(old=None) also needs the dataset "
                         "size; build the store first and call "
                         "reshard_sampler on the joiner's sampler")
    new = ScoreStore(n_global, host_id=int(me_uid), ema=old.ema,
                     staleness=old.staleness, members=members)
    gather = allgather or allgather_owned
    global_vec = np.asarray(gather(vals, gids, pad_to=int(pad_to),
                                   n_global=n_global,
                                   n_hosts=len(members)), np.float64)
    seen_ids = np.flatnonzero(global_vec >= 0)
    new.update(seen_ids, global_vec[seen_ids])
    old_uids = member_uids(old.ownership)
    sizes = old.shard_sizes()
    n_lost = int(sum(int(sizes[r]) for r, u in enumerate(old_uids)
                     if u not in members))
    return new, int(seen_ids.size), n_lost


def reshard_sampler(sampler, event: MembershipEvent, *, allgather=None,
                    pad_to=None) -> dict:
    """Point a live sampler at the new membership: migrate its store,
    update the (rank, n_hosts) view of sampler/source/assembler,
    re-resolve the selection impl exactly as a cold start at this
    membership would, and mark the τ-gate for refresh (coverage may have
    dropped). Mutates in place; the caller restarts the data plane at
    the current plan cursor afterwards. Returns a stats dict.
    """
    members = tuple(sorted(int(u) for u in event.members))
    if not members:
        raise ValueError("membership event carries no members — the caller "
                         "resolves unknown survivors (solo degrade) before "
                         "resharding")
    H = len(members)
    uid = int(getattr(sampler.store.ownership, "me_uid",
                      sampler.store.host_id))
    if uid not in members:
        raise ValueError(f"host uid {uid} is not among the survivors "
                         f"{members} — a departing host cannot reshard")
    if sampler.b % H:
        raise ValueError(
            f"global batch {sampler.b} not divisible by the new membership "
            f"of {H} hosts — rebalance the batch (rebalance_microbatches) "
            f"before resharding")
    rank = members.index(uid)
    new_store, n_migrated, n_lost = migrate_store(
        sampler.store, members, uid, allgather=allgather, pad_to=pad_to)
    sampler.store = new_store
    sampler.host_id = rank
    sampler.n_hosts = H
    sampler.source.host_id = rank
    sampler.source.n_hosts = H
    sampler.assembler.host_id = rank
    sampler.assembler.n_hosts = H
    from repro.sampler import selection
    sampler.impl = selection.resolve_selection_impl(
        sampler.icfg.selection_impl, n=sampler.source.n, b=sampler.b,
        n_hosts=H)
    if hasattr(sampler, "k_local"):
        sampler.k_local = sampler.b // H
    if sampler.scheme == "presample_fused":
        # single-host pools are device-resident + pre-plannable again
        sampler.plan_is_pure = (H == 1)
    if hasattr(sampler, "_gate_dirty"):
        sampler._gate_dirty = True
    obs.counter("runtime.membership.events").inc()
    obs.gauge("runtime.membership.n_hosts").set(H)
    obs.counter("runtime.membership.migrated_ids").inc(n_migrated)
    obs.counter("runtime.membership.lost_ids").inc(n_lost)
    return {"members": members, "rank": rank, "n_hosts": H,
            "migrated": n_migrated, "lost": n_lost,
            "coverage": new_store.coverage()}


def solo_event(event: MembershipEvent, uid: int) -> MembershipEvent:
    """Resolve an unknown-survivor event (a bare collective timeout:
    ``members == ()``) to the bottom rung of the degradation ladder — a
    solo pod of this host. Known-survivor events pass through."""
    if event.members:
        return event
    import dataclasses
    return dataclasses.replace(event, members=(int(uid),))


# ---------------------------------------------------------------------------
# device-count remesh + checkpoint recovery (the restart-based fallback)
# ---------------------------------------------------------------------------
def remesh_shape(n_devices: int, model_degree: int):
    """Largest (data, model) split for ``n_devices`` keeping TP if possible."""
    model = model_degree
    while model > 1 and n_devices % model != 0:
        model //= 2
    return n_devices // model, model


def remesh(n_devices: int, model_degree: int):
    from repro.launch.mesh import build_mesh
    data, model = remesh_shape(n_devices, model_degree)
    return build_mesh((data, model), ("data", "model")), data, model


def rebalance_microbatches(global_batch: int, old_dp: int, old_micro: int,
                           new_dp: int) -> int:
    """Keep the global batch and per-device memory constant when dp shrinks:
    micro-batches scale by old_dp/new_dp (rounded up to a divisor)."""
    target = max(1, (old_micro * old_dp + new_dp - 1) // new_dp)
    local = max(1, global_batch // new_dp)
    while local % target != 0 and target < local:
        target += 1
    return min(target, local)


def recover(ckpt, template_state, mesh, state_specs):
    """Restore the latest committed checkpoint onto ``mesh``."""
    from repro.distributed.sharding import to_named
    shardings = to_named(state_specs, mesh)
    state, step = ckpt.restore(template_state, shardings=shardings)
    return state, step
