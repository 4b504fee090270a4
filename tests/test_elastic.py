"""The elastic membership runtime.

Fault-injection grammar + plane semantics, the collective deadline/retry
envelope and its escalation to ``MembershipChange``, HRW rendezvous
ownership invariants, score migration exactness, remesh/rebalance edge
cases, straggler escalation, topology-mismatch checkpoint routing, and
the loop's catch → reshard → replay path.
"""
import json

import numpy as np
import pytest

import repro
from repro.configs.base import FaultsConfig, RuntimeConfig
from repro.distributed import collectives
from repro.runtime import elastic, faults
from repro.runtime.membership import MembershipChange, MembershipEvent
from repro.sampler.store import RendezvousOwnership, ScoreStore


@pytest.fixture(autouse=True)
def _clean_runtime():
    """Faults/envelope state is process-global; never leak across tests."""
    yield
    faults.configure(None)
    collectives.configure(None)


# ---------------------------------------------------------------------------
# fault schedule grammar + plane
# ---------------------------------------------------------------------------
def test_parse_spec_grammar():
    assert faults.parse_spec("") == ()
    assert faults.parse_spec(" timeout@3:1 ; gather@4 ;die@8:1; "
                             "slow@5:0:0.4") == (
        ("timeout", 3, 1, 0.0), ("gather", 4, -1, 0.0),
        ("die", 8, 1, 0.0), ("slow", 5, 0, 0.4))
    with pytest.raises(faults.FaultSpecError, match="unknown fault kind"):
        faults.parse_spec("explode@3")
    with pytest.raises(faults.FaultSpecError, match="bad fault entry"):
        faults.parse_spec("timeout@soon")


def test_fault_plane_firing_budgets():
    plane = faults.FaultPlane(
        FaultsConfig(enabled=True, spec="timeout@2:0:3;gather@5"), host_id=0)
    plane.set_step(2)
    # timeout entries fire `arg` consecutive attempts, then recover
    assert [plane.match("timeout") is not None for _ in range(5)] == \
        [True, True, True, False, False]
    # other kinds fire exactly once
    assert plane.match("gather", step=5) is not None
    assert plane.match("gather", step=5) is None
    # wrong step / wrong kind never fire
    assert plane.match("timeout", step=3) is None
    assert plane.match("die", step=2) is None


def test_fault_plane_duplicate_entries_fire_independently():
    """N identical entries = N scheduled firings (how a test makes every
    retry attempt of one step slow)."""
    plane = faults.FaultPlane(
        FaultsConfig(enabled=True, spec="slow@2:0:9;slow@2:0:9"), host_id=0)
    assert [plane.match("slow", step=2) is not None for _ in range(3)] == \
        [True, True, False]


def test_fault_plane_host_filter():
    cfg = FaultsConfig(enabled=True, spec="gather@1:1;slow@1:-1:0.2")
    other = faults.FaultPlane(cfg, host_id=0)
    target = faults.FaultPlane(cfg, host_id=1)
    assert other.match("gather", step=1) is None       # host-1-only entry
    assert target.match("gather", step=1) is not None
    assert other.match("slow", step=1) is not None     # -1 = every host
    assert target.match("slow", step=1) is not None


def test_faults_module_disabled_is_inert():
    faults.configure(None)
    assert not faults.active()
    faults.raise_if("timeout")                          # no-op, no raise
    faults.set_step(3)
    assert faults.slow_penalty() == 0.0
    assert not faults.should("gather")
    # enabled=False configs uninstall too
    faults.configure(FaultsConfig(enabled=False, spec="gather@0"))
    assert not faults.active()


def test_faults_module_api():
    faults.configure(FaultsConfig(enabled=True, spec="timeout@1;slow@2:0:0.7"),
                     host_id=0)
    faults.set_step(1)
    with pytest.raises(faults.FaultInjected, match="step 1 in exchange"):
        faults.raise_if("timeout", op="exchange")
    faults.raise_if("timeout")                          # consumed above
    assert faults.slow_penalty(step=2) == pytest.approx(0.7)
    assert faults.slow_penalty(step=2) == 0.0           # one-shot


# ---------------------------------------------------------------------------
# the collective deadline/retry envelope
# ---------------------------------------------------------------------------
def _fast_runtime(retries=2):
    return RuntimeConfig(collective_timeout_s=0.05,
                         collective_retries=retries,
                         backoff_base_s=0.001, backoff_max_s=0.002)


def test_envelope_recovers_within_retry_budget(monkeypatch):
    """Attempts that fail inside the retry budget are retried with
    backoff and the collective SUCCEEDS — the pod never sees the blip."""
    collectives.configure(_fast_runtime(retries=2))
    faults.configure(FaultsConfig(enabled=True, spec="timeout@0:0:2"))
    faults.set_step(0)
    calls = []
    monkeypatch.setattr(collectives, "_kv_allgather",
                        lambda v: (calls.append(1), np.stack([v, v]))[1])
    out = collectives._process_allgather(np.arange(3.0), op="test_op")
    assert out.shape == (2, 3)
    assert calls == [1]          # two injected failures, third attempt ran


def test_envelope_escalates_to_membership_change(monkeypatch):
    """Persistent deadline breaches must NOT hang or crash-loop: after
    the retry budget the funnel raises ``MembershipChange`` with unknown
    survivors (the degradation ladder's caller resolves solo)."""
    collectives.configure(_fast_runtime(retries=1))
    faults.configure(FaultsConfig(enabled=True, spec="timeout@0:0:99"))
    faults.set_step(0)
    monkeypatch.setattr(collectives, "_kv_allgather",
                        lambda v: pytest.fail("backend must not be reached"))
    with pytest.raises(MembershipChange) as ei:
        collectives._process_allgather(np.zeros(1), op="test_op")
    event = ei.value.event
    assert event.kind == "timeout"
    assert event.members == ()               # survivors unknown at raise
    assert "test_op" in event.reason


def test_envelope_reraises_real_bugs(monkeypatch):
    """Non-deadline errors are bugs, not membership events."""
    collectives.configure(_fast_runtime())

    def boom(v):
        raise TypeError("wrong dtype")
    monkeypatch.setattr(collectives, "_kv_allgather", boom)
    with pytest.raises(TypeError, match="wrong dtype"):
        collectives._process_allgather(np.zeros(1), op="test_op")


def test_solo_event_resolution():
    unknown = MembershipEvent(kind="timeout", reason="deadline")
    solo = elastic.solo_event(unknown, uid=3)
    assert solo.members == (3,) and solo.n_hosts == 1
    known = MembershipEvent(kind="leave", members=(0, 2))
    assert elastic.solo_event(known, uid=0) is known


# ---------------------------------------------------------------------------
# rendezvous (HRW) ownership
# ---------------------------------------------------------------------------
def test_rendezvous_ownership_partitions_ids():
    n, members = 101, (3, 7, 9)
    owners = [RendezvousOwnership(n, members, me_uid=u) for u in members]
    all_ids = np.concatenate([o.my_global_ids() for o in owners])
    np.testing.assert_array_equal(np.sort(all_ids), np.arange(n))
    for o in owners:
        mine = o.my_global_ids()
        assert o.owned(mine).all()
        np.testing.assert_array_equal(o.global_ids(o.slot(mine)), mine)
        assert o.n_local == mine.size
    sizes = owners[0].shard_sizes()
    assert int(sizes.sum()) == n
    # every member computes the identical assignment
    for o in owners[1:]:
        np.testing.assert_array_equal(o.owner, owners[0].owner)


def test_rendezvous_minimal_movement_on_leave():
    """The HRW property the migration cost bound rests on: when a member
    leaves, ids owned by the SURVIVORS stay put — only the departed
    host's ids re-home."""
    n = 257
    before = {u: set(RendezvousOwnership(n, (0, 1, 2, 3), me_uid=u)
                     .my_global_ids().tolist()) for u in (0, 1, 3)}
    after = {u: set(RendezvousOwnership(n, (0, 1, 3), me_uid=u)
                    .my_global_ids().tolist()) for u in (0, 1, 3)}
    for u in (0, 1, 3):
        assert before[u] <= after[u]


def test_rendezvous_rejects_bad_membership():
    with pytest.raises(ValueError):
        RendezvousOwnership(10, (0, 0, 1), me_uid=0)     # duplicate uid
    with pytest.raises(ValueError):
        RendezvousOwnership(10, (0, 1), me_uid=5)        # not a member


# ---------------------------------------------------------------------------
# score migration
# ---------------------------------------------------------------------------
def test_migrate_store_exact_for_survivors():
    n, h_old = 40, 4
    rng = np.random.default_rng(0)
    stores = [ScoreStore(n, host_id=h, n_hosts=h_old) for h in range(h_old)]
    truth = rng.uniform(0.1, 5.0, n)
    seen = rng.random(n) < 0.7
    ids = np.flatnonzero(seen)
    for s in stores:
        s.update(ids, truth[ids])        # each keeps its owned slice
    survivors = (1, 2)
    mig = np.full(n, -1.0, np.float64)
    for u in survivors:
        mig[stores[u].my_global_ids()] = stores[u].sentinel_scores()
    new, n_migrated, n_lost = elastic.migrate_store(
        stores[1], survivors, me_uid=1, allgather=lambda v, g, **kw: mig)
    assert new.ownership.kind == "rendezvous"
    # exact carry-over: every surviving seen entry, bitwise-as-f32
    surv_seen = [g for u in survivors
                 for g in stores[u].my_global_ids()[
                     stores[u].seen.astype(bool)]]
    assert n_migrated == len(surv_seen)
    assert n_lost == sum(stores[u].n_local for u in (0, 3))
    got = new.sentinel_scores()
    mine = new.my_global_ids()
    expect = mig[mine].astype(np.float32)
    np.testing.assert_array_equal(got, np.where(expect >= 0, expect,
                                                np.float32(-1.0)))


def test_migrate_store_rejects_joiner_without_shard():
    with pytest.raises(ValueError, match="joining host"):
        elastic.migrate_store(None, (0, 1), me_uid=1)


def test_reshard_sampler_validates():
    from repro.data.pipeline import SyntheticLM
    from repro.sampler import make_sampler
    from tests.test_plan import _run_cfg
    run = _run_cfg("uniform", impl="gather")
    sp = make_sampler(run, SyntheticLM(run.model.vocab_size, 16,
                                       n_examples=64, seed=0))
    with pytest.raises(ValueError, match="no members"):
        elastic.reshard_sampler(sp, MembershipEvent(kind="leave"))
    with pytest.raises(ValueError, match="not among the survivors"):
        elastic.reshard_sampler(sp, MembershipEvent(kind="leave",
                                                    members=(4, 5)))
    with pytest.raises(ValueError, match="not divisible"):
        elastic.reshard_sampler(sp, MembershipEvent(kind="join",
                                                    members=(0, 1, 2)))


# ---------------------------------------------------------------------------
# remesh / rebalance edge cases
# ---------------------------------------------------------------------------
def test_remesh_shape_prime_and_oversized_model_degree():
    assert elastic.remesh_shape(7, 4) == (7, 1)      # prime count: TP gone
    assert elastic.remesh_shape(13, 8) == (13, 1)
    assert elastic.remesh_shape(2, 8) == (1, 2)      # model > devices
    assert elastic.remesh_shape(3, 8) == (3, 1)
    assert elastic.remesh_shape(1, 16) == (1, 1)


def test_rebalance_microbatches_indivisible_batch():
    # global batch not divisible by the shrunken dp: micro target rounds
    # up to a divisor of the local batch and never exceeds it
    assert elastic.rebalance_microbatches(100, 16, 4, 6) == 16
    assert elastic.rebalance_microbatches(8, 8, 1, 3) == 2
    assert elastic.rebalance_microbatches(96, 8, 2, 5) == 19


# ---------------------------------------------------------------------------
# straggler escalation
# ---------------------------------------------------------------------------
def test_straggler_escalates_after_shrink_and_skip_budget():
    from repro.runtime.straggler import StragglerMonitor
    m = StragglerMonitor(deadline_factor=2.0, max_skips=2)
    for _ in range(6):
        m.observe(1.0)                       # warm the EMA
    seq = [m.observe(10.0) for _ in range(5)]
    # rung 1: shrink B to the floor; rung 2: skip budget; rung 3: escalate
    assert [a["b_scale"] for a in seq[:2]] == [pytest.approx(0.5),
                                               pytest.approx(1 / 3)]
    assert [a["skip"] for a in seq] == [False, False, True, True, False]
    assert [a["escalate"] for a in seq] == [False] * 4 + [True]


def test_straggler_hook_raises_membership_change():
    from repro.api.hooks import StragglerHook

    class _Exp:
        pass

    class _Loop:
        pass

    class _Mon:
        def observe(self, dt):
            return {"skip": False, "b_scale": 1 / 3, "over_deadline": True,
                    "escalate": True}

    class _Samp:
        store = ScoreStore(16, host_id=0, n_hosts=1)

    loop = _Loop()
    loop.exp = _Exp()
    loop.exp.monitor = _Mon()
    loop.exp.sampler = _Samp()
    with pytest.raises(MembershipChange) as ei:
        StragglerHook().on_step_timed(loop, 7, 2, 9.9)
    assert ei.value.event.kind == "straggler"
    assert ei.value.event.members == (0,)


def test_straggler_hook_tolerates_legacy_action_dicts():
    """Fake monitors that predate the ``escalate`` key keep working."""
    from repro.api.hooks import StragglerHook

    class _Loop:
        class exp:
            class monitor:
                @staticmethod
                def observe(dt):
                    return {"skip": True, "b_scale": 1.0,
                            "over_deadline": True}

    assert StragglerHook().on_step_timed(_Loop(), 0, 0, 1.0) is True


# ---------------------------------------------------------------------------
# checkpoint topology routing
# ---------------------------------------------------------------------------
def test_checkpointer_reaps_orphaned_tmp_dirs(tmp_path):
    from repro.checkpoint.ckpt import Checkpointer
    orphan = tmp_path / "step_5.tmp-deadbeef"
    orphan.mkdir()
    (orphan / "shard_0.npz").write_bytes(b"partial")
    Checkpointer(tmp_path)
    assert not orphan.exists()


def test_restore_raises_topology_mismatch(tmp_path):
    from repro.checkpoint.ckpt import Checkpointer, TopologyMismatch
    ck = Checkpointer(tmp_path)
    ck.save(3, {"w": np.arange(4.0)})
    man_path = tmp_path / "step_3" / "manifest.json"
    man = json.loads(man_path.read_text())
    man["n_hosts"] = 2
    man_path.write_text(json.dumps(man))
    with pytest.raises(TopologyMismatch, match="written by 2"):
        ck.restore({"w": np.zeros(4)})
    state, step = ck.restore({"w": np.zeros(4)}, check_topology=False)
    assert step == 3
    np.testing.assert_array_equal(state["w"], np.arange(4.0))
    assert ck.manifest(3)["n_hosts"] == 2


def test_resume_routes_topology_mismatch_through_reshard(tmp_path):
    """A restart into a different pod size must not restore the sampler
    blind (the merged shard view keeps one host's scores and calls it
    the world) NOR start cold: every old host's shard file is on disk,
    so the global score memory reassembles exactly."""
    from repro.api.experiment import Experiment, _resolve_run
    ckdir = str(tmp_path / "ck")
    over = {"ckpt_dir": ckdir, "ckpt_every": 2}
    exp, state, hist = repro.train("lm-tiny", preset="smoke", steps=4,
                                   overrides=over, return_experiment=True)
    sentinel = exp.sampler.store.sentinel_scores().copy()
    assert (sentinel >= 0).any()             # training warmed the store
    step_dir = tmp_path / "ck" / "step_4"
    # rewrite the checkpoint as if TWO hosts (old strided layout) wrote it
    with np.load(step_dir / "shard_0.npz") as z:
        data = {k: z[k] for k in z.files}
    scores = data["sampler/store/scores"]
    seen = data["sampler/store/seen"]
    for h in range(2):
        shard = dict(data) if h == 0 else {}
        shard["sampler/store/scores"] = scores[h::2]
        shard["sampler/store/seen"] = seen[h::2]
        np.savez(step_dir / f"shard_{h}.npz", **shard)
    man_path = step_dir / "manifest.json"
    man = json.loads(man_path.read_text())
    man["n_hosts"] = 2
    man_path.write_text(json.dumps(man))
    # a fresh process (1 host) resumes: train state restored, store warm
    exp2 = Experiment(_resolve_run("lm-tiny", "smoke", over))
    state2, pstate2, start2 = exp2.resume_or_init()
    assert start2 == 4
    np.testing.assert_array_equal(exp2.sampler.store.sentinel_scores(),
                                  sentinel)
    np.testing.assert_array_equal(
        np.asarray(state2["params"]["embed"]),
        np.asarray(state["params"]["embed"]))


# ---------------------------------------------------------------------------
# the loop's membership path
# ---------------------------------------------------------------------------
def test_loop_catches_membership_change_and_replays_step(monkeypatch):
    """A MembershipChange mid-step reshards (solo degrade at H=1),
    restarts the plane at the SAME plan cursor, replays the step, and the
    run completes all steps — with the event visible to hooks."""
    from repro.api.experiment import Experiment, _resolve_run
    exp = Experiment(_resolve_run("lm-tiny", "smoke", {"steps": 6}))
    fired = {"n": 0}
    orig = collectives.allreduce_any

    def chaos(flag, *, n_hosts=None):
        if fired["n"] == 0:
            fired["n"] += 1
            raise MembershipChange(MembershipEvent(kind="timeout",
                                                   reason="injected"))
        return orig(flag, n_hosts=n_hosts)
    monkeypatch.setattr(collectives, "allreduce_any", chaos)
    events = []

    class Rec(repro.Hook):
        def on_membership_change(self, loop, step, event, stats):
            events.append((step, event.kind, event.members,
                           stats["n_hosts"]))
    state, hist = exp.fit(steps=6, hooks=[Rec()])
    assert len(hist) == 6
    assert [m["step"] for m in hist] == list(range(6))
    assert events == [(0, "timeout", (0,), 1)]


def test_loop_keeps_donated_update_on_membership_change():
    """With a donated train state (max_step_retries == 0) the step's input
    is gone once it dispatches: a MembershipChange raised after that (here
    by a step_timed hook, as straggler escalation does) keeps the update
    and resumes at the next step instead of replaying from a deleted
    state."""
    from repro.api.experiment import Experiment, _resolve_run
    exp = Experiment(_resolve_run("lm-tiny", "smoke",
                                  {"steps": 6, "max_step_retries": 0}))
    assert exp.donates_state
    events = []

    class Escalate(repro.Hook):
        def on_step_timed(self, loop, step, attempt, dt):
            if step == 2 and not events:
                raise MembershipChange(MembershipEvent(kind="straggler",
                                                       reason="injected"))
            return False

        def on_membership_change(self, loop, step, event, stats):
            events.append((step, event.kind))
    state, hist = exp.fit(steps=6, hooks=[Escalate()])
    assert events == [(3, "straggler")]
    assert [m["step"] for m in hist] == [0, 1, 3, 4, 5]
    assert int(state["step"]) == 6
    assert all(np.isfinite(m["loss"]) for m in hist)


def test_plane_surfaces_injected_gather_fault_then_retries():
    """The data plane's surface-then-retry contract under the harness:
    the consumer sees the injected fault once, and the very next pop is
    the successfully retried plan — same cursor, nothing skipped."""
    from repro.data.pipeline import DataPlane, PipelineState, SyntheticLM
    from repro.sampler import make_sampler
    from tests.test_plan import _run_cfg
    faults.configure(FaultsConfig(enabled=True, spec="gather@1"))
    run = _run_cfg("uniform", impl="gather")
    sp = make_sampler(run, SyntheticLM(run.model.vocab_size, 16,
                                       n_examples=64, seed=0))
    plane = DataPlane(sp, depth=1)
    plane.start(PipelineState(), 0)
    try:
        _, plan0, _ = plane.next()
        assert plan0.step == 0
        with pytest.raises(faults.FaultInjected, match="step 1"):
            plane.next()
        _, plan1, _ = plane.next()
        assert plan1.step == 1               # retried, not dropped
    finally:
        plane.stop()
