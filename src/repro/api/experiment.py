"""The ``Experiment`` facade — repro's one public composition root.

An ``Experiment`` wires model / data source / mesh / sampler / scoring
engine / optimizer / checkpointing together from a single ``RunConfig``
and runs the event-hook ``TrainLoop`` over them. The paper's pitch is
that importance sampling is "a few changed lines in a standard SGD
procedure"; this is the few-lines entry point:

    import repro
    state, history = repro.train("lm-tiny", preset="paper_cifar",
                                 source="cls")

Entry points:

* ``repro.train(...)`` / ``repro.score(...)`` / ``repro.serve(...)`` —
  one-call functions (this module + ``repro.api.serving``).
* ``Experiment(run_cfg, ...)`` — programmatic composition; exposes the
  parts (``lm``, ``sampler``, ``engine``, ``step_fn``, ``monitor``) for
  surgery in tests/benchmarks.
* ``Experiment.from_flags(argv)`` — the auto-generated CLI: reserved
  flags (``--arch --preset --smoke --mesh --source``) plus dotted
  dataclass overrides (``--imp.presample_ratio=5``); unknown keys are
  hard errors.
* ``Experiment.from_checkpoint(dir)`` — rebuild a run from the lossless
  config serialized into its checkpoint manifest.

Hot-path notes (overlapped scoring, deferred feedback, straggler retry
semantics) live on ``repro.api.loop.TrainLoop``, which preserves the old
``Trainer.fit`` behaviour step-for-step. ``repro.runtime.trainer.Trainer``
remains as a deprecated alias of this class.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.api.config import (ConfigError, apply_overrides, build_run,
                              from_dict, get_preset, parse_cli, truthy)
from repro.checkpoint.ckpt import Checkpointer, TopologyMismatch
from repro.configs.base import ModelConfig, RunConfig
from repro.core.is_train import StepSpec, build_step, train_state_init
from repro.data.pipeline import (DataPlane, PipelineState, SyntheticCLS,
                                 SyntheticLM)
from repro.distributed import sharding as shd
from repro.models.lm import LM
from repro.optim.api import get_optimizer
from repro.runtime.straggler import StragglerMonitor
from repro.sampler import make_sampler
from repro.scoring import ScoreEngine

def make_mesh(kind):
    """Mesh-kind name -> device mesh: ``none``/None, ``host``, ``pod``,
    ``multipod``."""
    from repro.launch.mesh import make_host_mesh, make_production_mesh
    if kind in (None, "none"):
        return None
    if kind == "host":
        return make_host_mesh()
    if kind in ("pod", "multipod"):
        return make_production_mesh(multi_pod=kind == "multipod")
    raise ConfigError(f"unknown mesh kind {kind!r}")


def _make_source(run: RunConfig, kind):
    """Resolve a source spec: an object with the source API is passed
    through; "lm"/"cls" build the synthetic sources from the run config."""
    if kind is None or kind == "lm":
        return SyntheticLM(run.model.vocab_size, run.shape.seq_len,
                           seed=run.seed)
    if kind == "cls":
        return SyntheticCLS(run.model.vocab_size, run.shape.seq_len,
                            seed=run.seed)
    if hasattr(kind, "gather"):
        return kind
    raise ConfigError(f"unknown data source {kind!r} (expected 'lm', 'cls', "
                      f"or a source object)")


def _resolve_run(cfg, preset=None, overrides=None) -> RunConfig:
    """str arch id | ModelConfig | RunConfig (+ preset + overrides) ->
    RunConfig."""
    if isinstance(cfg, RunConfig):
        if preset is not None:
            raise ConfigError("preset and a full RunConfig are exclusive — "
                              "presets BUILD RunConfigs")
        run = cfg
    elif isinstance(cfg, ModelConfig):
        run = get_preset(preset)(cfg) if preset else RunConfig(model=cfg)
    else:
        run = build_run(arch=cfg, preset=preset)
    return apply_overrides(run, overrides)


class Experiment:
    """Model + source + mesh + sampler + engine + loop, from one config."""

    def __init__(self, run_cfg, source=None, mesh=None, gate=None, hooks=()):
        self.run = run_cfg
        # apply the telemetry switch before any instrumented part is built
        # (handles work either way, but the registry state should reflect
        # the run config from the first instant of the run)
        from repro import obs
        obs.configure(run_cfg.obs)
        # arm the elastic runtime before any collective can fire: the
        # deadline/retry envelope on every collective op, and the (off by
        # default, zero-cost when off) deterministic fault plane
        from repro.distributed import collectives
        from repro.runtime import faults
        collectives.configure(run_cfg.runtime)
        faults.configure(run_cfg.runtime.faults,
                         host_id=jax.process_index())
        self.lm = LM(run_cfg.model)
        self.opt = get_optimizer(run_cfg.optim)
        self.mesh = mesh
        self.gate = gate
        self.source = _make_source(run_cfg, source)
        # what goes into the checkpoint manifest so from_checkpoint can
        # rebuild the same data distribution (custom objects can't be
        # serialized — they must be re-passed explicitly on rebuild)
        self.source_spec = source if isinstance(source, str) else (
            "lm" if source is None else "custom:" + type(source).__name__)
        self.sampler = make_sampler(run_cfg, self.source)
        # the decoupled scoring path: host-side schemes score through it,
        # and it backs out-of-band ScoreStore refreshes (jit is lazy, so
        # binding it is free for schemes that never score on host)
        self.engine = ScoreEngine(self.lm, run_cfg, mesh=mesh)
        self.sampler.bind_engine(self.engine)
        self.B = run_cfg.shape.global_batch * run_cfg.imp.presample_ratio
        self.monitor = StragglerMonitor(run_cfg.step_deadline_factor)
        self.ckpt = (Checkpointer(run_cfg.ckpt_dir, keep=run_cfg.keep_ckpts)
                     if run_cfg.ckpt_dir else None)
        self.default_hooks = list(hooks)
        self.last_state = None       # final train state of the last fit()
        self._build()

    # -- constructors ---------------------------------------------------------
    @classmethod
    def from_flags(cls, argv=None, **kw):
        """Build an ``Experiment`` from CLI flags.

        Reserved flags: ``--arch <id>`` (required), ``--preset <name>``,
        ``--smoke`` (= preset ``smoke`` + no mesh), ``--mesh
        none|host|pod|multipod`` (default none), ``--source lm|cls``.
        Every other flag must be a dotted ``RunConfig`` path
        (``--steps 200``, ``--imp.presample_ratio=5``,
        ``--sampler.scheme=history``) — unknown keys raise ``ConfigError``.
        """
        import sys
        argv = list(sys.argv[1:]) if argv is None else list(argv)
        flags = parse_cli(argv)
        arch = flags.pop("arch", None)
        preset = flags.pop("preset", None)
        smoke = truthy(flags.pop("smoke", False))
        mesh_kind = flags.pop("mesh", "none")
        source_kind = flags.pop("source", "lm")
        if arch is None:
            raise ConfigError("--arch is required (one of repro.configs.ARCHS)")
        if smoke:
            preset = preset or "smoke"
            mesh_kind = "none"
        run = build_run(arch=arch, preset=preset, overrides=flags)
        mesh = make_mesh(mesh_kind)
        if mesh is not None and "microbatches" not in flags:
            from repro.launch.mesh import (choose_microbatches,
                                           data_parallel_size)
            run = dataclasses.replace(run, microbatches=choose_microbatches(
                run.model, data_parallel_size(mesh), run.shape.global_batch))
        return cls(run, source=source_kind, mesh=mesh, **kw)

    @classmethod
    def from_checkpoint(cls, ckpt_dir, source=None, mesh=None, **kw):
        """Rebuild the exact run serialized into a checkpoint's manifest
        (``run_config`` + ``source`` meta keys, written by every
        ``TrainLoop`` save); ``fit()`` then resumes from that checkpoint."""
        meta = Checkpointer(ckpt_dir).meta()
        if "run_config" not in meta:
            raise ConfigError(f"checkpoint {ckpt_dir} predates the config "
                              f"manifest (no 'run_config' meta)")
        if source is None:
            spec = meta.get("source", "lm")
            if isinstance(spec, str) and spec.startswith("custom:"):
                raise ConfigError(
                    f"checkpoint {ckpt_dir} was trained with a custom data "
                    f"source ({spec[len('custom:'):]}) that cannot be "
                    f"rebuilt from the manifest — pass source= explicitly")
            source = spec
        run = dataclasses.replace(from_dict(meta["run_config"]),
                                  ckpt_dir=str(ckpt_dir))
        return cls(run, source=source, mesh=mesh, **kw)

    # -- step compilation ------------------------------------------------------
    def _build(self):
        # presample runs the paper's on-device Algorithm 1; the score-memory
        # and host-presample schemes use the host-chosen-batch step with a
        # sampled/weighted flag — both flavours of the ONE unified step
        if self.sampler.uses_score_step:
            spec = StepSpec("host")
        else:
            spec = StepSpec("presample", gate=self.gate or (
                "cond" if self.run.imp.enabled else "never"))
        step = build_step(self.lm, self.run, self.opt, spec)
        self.step_is_flagged = spec.flagged
        extra_in = (None,) if spec.flagged else ()  # is_flag scalar
        # the step consumes its train state: donated, XLA updates params
        # and optimizer state in place — at full width that is the
        # difference between fitting one chip and not. A donated state
        # cannot be restored, so a run that may retry a straggling step
        # (max_step_retries > 0) keeps its input state instead.
        self.donates_state = self.run.max_step_retries == 0
        donate = (0,) if self.donates_state else ()
        self.state_shardings = None
        if self.mesh is not None:
            key = jax.random.PRNGKey(self.run.seed)
            state_sds = jax.eval_shape(
                lambda k: train_state_init(self.lm, self.opt, k), key)
            self.state_shardings = shd.to_named(
                shd.state_specs(self.run.model, state_sds, self.mesh),
                self.mesh)
            step_fn = jax.jit(step,
                              in_shardings=(self.state_shardings, None)
                              + extra_in,
                              out_shardings=(self.state_shardings, None),
                              donate_argnums=donate,
                              compiler_options=shd.compiler_options(self.mesh))
        else:
            step_fn = jax.jit(step, donate_argnums=donate)
        self.step_fn = shd.under_mesh(step_fn, self.mesh)

    # -- data plane ------------------------------------------------------------
    def make_plane(self) -> DataPlane:
        """A fresh per-run data plane over this experiment's sampler.

        Pure-plan schemes (uniform / presample) get the depth-N pipelined
        plan → gather → device-put stages (``run.data``); store- and
        engine-coupled schemes pass through the sampler's two-phase
        ``begin``/``finish`` (which already overlap engine scoring).
        The loop owns the plane's lifetime (one per ``run()``).
        """
        return DataPlane(self.sampler, depth=self.run.data.prefetch_depth,
                         device_put=self.run.data.device_put)

    # -- state ----------------------------------------------------------------
    def init_state(self):
        """Fresh train state (laid out on the mesh's shardings, when there
        is a mesh) and pipeline cursor."""
        key = jax.random.PRNGKey(self.run.seed)
        init = lambda k: train_state_init(self.lm, self.opt, k)
        if self.state_shardings is not None:
            init = jax.jit(init, out_shardings=self.state_shardings)
        return init(key), PipelineState()

    def checkpoint_payload(self, state):
        """Checkpoint payload: train state + the sampler's score memory."""
        return {"train": state, "sampler": self.sampler.state_dict()}

    def on_membership_change(self, event):
        """The loop's membership handler: resolve the survivor set (an
        unknown-survivor timeout degrades to a solo pod of this host),
        reshard the sampler in place through the elastic path, and rebuild
        the straggler monitor (its deadline EMA described the old pod).
        Returns ``(resolved event, reshard stats)``; the loop restarts the
        data plane at its current plan cursor afterwards."""
        from repro.runtime import elastic
        old = set(elastic.member_uids(self.sampler.store.ownership))
        uid = int(getattr(self.sampler.store.ownership, "me_uid",
                          self.sampler.store.host_id))
        event = elastic.solo_event(event, uid)
        event = dataclasses.replace(
            event, departed=tuple(sorted(old - set(event.members))))
        stats = self.sampler_reshard(event)
        self.monitor = StragglerMonitor(self.run.step_deadline_factor)
        return event, stats

    def sampler_reshard(self, event):
        """Reshard the sampler onto ``event.members`` (overridable seam:
        tests inject simulated collectives through ``elastic`` directly)."""
        from repro.runtime import elastic
        return elastic.reshard_sampler(self.sampler, event)

    def resume_or_init(self):
        """Restart-from-checkpoint: the node-failure recovery entry point."""
        if self.ckpt and self.ckpt.latest_step() is not None:
            template, pstate = self.init_state()
            try:
                payload, step = self.ckpt.restore({"train": template})
                state = payload["train"]
            except TopologyMismatch as tm:
                return self._resume_resharded(tm, template, pstate)
            except KeyError:
                # legacy layout: train state at the payload root
                state, step = self.ckpt.restore(template)
            try:
                # lenient: a checkpoint from another scheme still warms the
                # shared score store; scheme-specific extras keep their init
                samp, _ = self.ckpt.restore(
                    {"sampler": self.sampler.state_dict()}, step=step,
                    strict=False)
                self.sampler.load_state_dict(samp["sampler"])
            except (KeyError, ValueError):
                pass  # different dataset/topology: sampler starts cold
            meta = self.ckpt.meta()
            pstate = PipelineState.from_dict(meta.get("pipeline", pstate.as_dict()))
            return state, pstate, step
        state, pstate = self.init_state()
        return state, pstate, 0

    def _resume_resharded(self, tm: TopologyMismatch, template, pstate):
        """Restart into a DIFFERENT pod size than the checkpoint's writers
        (``TopologyMismatch``): the membership-change-across-a-restart
        case. The train state merges fine (every old host's shard file is
        on disk, and train leaves share key names AND values), but the
        sampler's score shards were laid out for the old membership — so
        instead of the strict restore they route through the elastic
        degradation contract: reassemble the global sentinel vector from
        the old strided shards and adopt it via ``update`` (write-through
        on the cold store, so migration is exact; and since ALL old
        shards are on disk — unlike a live host death — nothing is lost).
        """
        from repro import obs
        payload, step = self.ckpt.restore({"train": template},
                                          check_topology=False)
        state = payload["train"]
        store = self.sampler.store
        n = store.n
        global_vec = np.full(n, -1.0, np.float64)
        h_old = tm.ckpt_hosts
        for h, arrs in self.ckpt.shards(step).items():
            scores = arrs.get("sampler/store/scores")
            seen = arrs.get("sampler/store/seen")
            if scores is None or seen is None:
                continue  # pre-plan-world layout: sampler starts cold
            gids = np.arange(scores.size, dtype=np.int64) * h_old + int(h)
            keep = (gids < n) & seen.astype(bool)
            global_vec[gids[keep]] = np.asarray(scores, np.float64)[keep]
        ids = np.flatnonzero(global_vec >= 0)
        if ids.size:
            store.update(ids, global_vec[ids])
        if hasattr(self.sampler, "_gate_dirty"):
            self.sampler._gate_dirty = True
        obs.counter("runtime.membership.events").inc()
        obs.gauge("runtime.membership.n_hosts").set(store.n_hosts)
        obs.counter("runtime.membership.migrated_ids").inc(int(ids.size))
        meta = self.ckpt.meta(step)
        pstate = PipelineState.from_dict(
            meta.get("pipeline", pstate.as_dict()))
        return state, pstate, step

    # -- entry points ----------------------------------------------------------
    def fit(self, steps=None, log_every=None, callback=None, hooks=()):
        """Train via the event-hook loop. Returns ``(state, history)`` —
        the same contract as the old ``Trainer.fit``."""
        from repro.api.hooks import (CallbackHook, CheckpointHook,
                                     LoggingHook, MetricsHistoryHook,
                                     StragglerHook)
        from repro.api.loop import TrainLoop
        hs = [MetricsHistoryHook()]
        if self.run.obs.enabled:
            # IS-health gauges first so every later hook (logging, user
            # hooks, the telemetry flush) sees the enriched metrics dict
            from repro.obs.health import VarianceGainHook
            hs.append(VarianceGainHook())
        if log_every:
            hs.append(LoggingHook(every=log_every))
        hs += list(self.default_hooks) + list(hooks)
        if callback is not None:
            hs.append(CallbackHook(callback))
        hs += [CheckpointHook(), StragglerHook()]
        if self.run.obs.enabled:
            # flush pump last: the registry snapshot it writes includes
            # everything the step's other hooks recorded
            from repro.obs.hook import TelemetryHook
            hs.append(TelemetryHook(self.run.obs))
        state, history = TrainLoop(self, hs).run(steps)
        self.last_state = state
        return state, history

    def score(self, params, batch):
        """Forward-only per-sample (loss, score) through the decoupled
        engine; blocking, numpy."""
        return self.engine.score_host(params, batch)

    def serve(self, params=None, **kw):
        """Prefill + batched greedy decode with this experiment's model
        (``repro.api.serving.serve``); defaults to the last trained params."""
        from repro.api.serving import serve as _serve
        if params is None and self.last_state is not None:
            params = self.last_state["params"]
        return _serve(self.run.model, params=params, mesh=self.mesh, **kw)


# ---------------------------------------------------------------------------
# one-call entry points (re-exported as repro.train / repro.score)
# ---------------------------------------------------------------------------
def train(cfg="lm-tiny", *, preset=None, overrides=None, source=None,
          mesh=None, gate=None, steps=None, callback=None, hooks=(),
          log_every=None, return_experiment=False):
    """Train in one call.

    ``cfg`` is an arch id (``"lm-tiny"``), a ``ModelConfig``, or a full
    ``RunConfig``; ``preset`` names a registered cell (``smoke``,
    ``paper_cifar``, ``demo``); ``overrides`` is a dotted-path dict
    (``{"imp.presample_ratio": 5}``). Returns ``(state, history)``, or
    ``(experiment, state, history)`` with ``return_experiment=True``.
    """
    run = _resolve_run(cfg, preset, overrides)
    exp = Experiment(run, source=source, mesh=mesh, gate=gate, hooks=hooks)
    state, history = exp.fit(steps=steps, callback=callback,
                             log_every=log_every)
    if return_experiment:
        return exp, state, history
    return state, history


def score(cfg="lm-tiny", *, params=None, batch=None, gids=None, source=None,
          preset=None, overrides=None, mesh=None):
    """Score examples in one call: forward-only per-sample (loss, score)
    through the decoupled ``ScoreEngine`` — no train step involved.

    ``batch`` wins if given; else ``gids`` are gathered from the source;
    else the source's first batch is scored. ``params=None`` scores a
    freshly initialised model (useful for pipeline smoke tests)."""
    run = _resolve_run(cfg, preset, overrides)
    lm = LM(run.model)
    engine = ScoreEngine(lm, run, mesh=mesh)
    if params is None:
        params = lm.init(jax.random.PRNGKey(run.seed))
    if batch is None:
        src = _make_source(run, source)
        if gids is not None:
            batch = src.gather(np.asarray(gids, np.int64))
        else:
            batch, _ = src.batch(PipelineState(), run.shape.global_batch)
    return engine.score_host(params, batch)
