"""NTPSession — the single runtime entry point for training under failures
(DESIGN.md §2).

One façade over the two training stacks:

* the **NTP prototype** (core/ntp_train.py): unit-buffered nonuniform TP
  inside shard_map, failure-event-driven replanning, pluggable optimizer;
* the **production arch stack** (train/steps.py make_setup): GSPMD-sharded
  arch-config models — uniform only (a failure there is a full restart; the
  NTP backend is the paper's mitigation).

Session lifecycle::

    session = NTPSession.create(cfg, mesh, local_batch=4,
                                optimizer=optim.adamw(AdamWConfig(lr=1e-2)))
    for i, batch in ...:
        if gpu_died:
            session.apply(FailureEvent(step=i, replica=r))   # replan in place
        if gpu_repaired:
            session.apply(RecoveryEvent(step=i, domain=d))   # TP back up
        metrics = session.step(batch)                        # loss, grad_norm
    session.save("ckpt.npz")                                 # canonical layout

`apply()` transitions FailurePlan -> FailurePlan' by moving params AND
optimizer state through the unified reshard engine's direct packed→packed
transition (repro.reshard, DESIGN.md §3.3) — the checkpoint-free equivalent
of the paper's restart; only units whose rank changes move, fused into one
bucketed send per rank pair (`session.last_transition` has the ledger).
It runs in BOTH directions: a `FailureEvent` lowers a replica's TP, a
`RecoveryEvent` raises it back toward full (DESIGN.md §2.4). An optional
`PowerPolicy` (runtime/orchestrator.py) is consulted on every transition to
pick per-replica power boost + usable batch (NTP vs NTP-PW) and annotate
step metrics with the boost level and predicted relative iteration time.

The full health-state taxonomy (DESIGN.md §2.11) rides the same `apply()`
path: `StragglerEvent`/`LinkDegradeEvent` leave the TP plan alone but
reprice the policy decision (batch shrink / boost) through the degradation
ledger; `SdcSuspectEvent` quarantines the replica (batch 0) and — when a
`snapshot()` restore point exists — rolls params+optimizer back to it
(`rollback()`, flagged in ``session.last_rollback``); each `*Clear`/
`*Repair` inverse unwinds its onset exactly.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro import telemetry
from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.core import ntp_train as nt
from repro.core.nonuniform import FailurePlan, StagedPlan, as_staged
from repro.core.ntp_train import Mode, NTPModelConfig
from repro.optim import AdamWConfig, Optimizer, adamw
from repro.runtime.events import (
    DEGRADATION_EVENTS, ClusterHealth, FailureEvent, LifecycleEvent,
    SdcSuspectEvent, StagedHealth, event_kind, plan_from_health,
    staged_plan_from_health,
)


class NTPSession:
    """Stateful training session: owns packed params + optimizer state, the
    jitted step for the current FailurePlan, and the health ledger."""

    # -------------------------------------------------------------- create

    def __init__(self, *_, **__):
        raise TypeError("use NTPSession.create(...) or NTPSession.from_arch(...)")

    @classmethod
    def _new(cls) -> "NTPSession":
        self = object.__new__(cls)
        self._host_step = 0       # steps dispatched: the profiler's step_num
        self._fresh_step = True   # the next step traces and compiles
        return self

    @classmethod
    def create(
        cls,
        cfg: NTPModelConfig,
        mesh,
        *,
        health: Optional[Union[ClusterHealth, StagedHealth]] = None,
        plan: Optional[Union[FailurePlan, StagedPlan]] = None,
        mode: Union[Mode, str] = Mode.NTP,
        local_batch: int = 4,
        optimizer: Optional[Optimizer] = None,
        params: Optional[Dict] = None,     # canonical; default random init
        key=None,
        power_policy=None,                 # orchestrator.PowerPolicy
        spares: int = 0,                   # spare domains absorbing failures
        pp: int = 1,                       # pipeline stages (DESIGN.md §2.6)
        microbatches: int = 1,             # 1F1B chunks per step (pp > 1)
        allocator=None,                    # cluster.GreedyAllocator (pp > 1)
        overlap: bool = False,             # overlapped bucketed sync (§2.10)
        quarantine: bool = True,           # SDC → batch 0 + rollback (§2.11)
    ) -> "NTPSession":
        """NTP-prototype session on a (data=D, model=N1) mesh. ``health``
        and/or ``plan`` seed the failure state (default: pristine).

        ``pp`` > 1 partitions the transformer into contiguous layer stages
        (boundaries from `configs.shapes.stage_boundaries`); health is then
        tracked per (replica, stage) and a failure reduces TP only for the
        stage whose scale-up domain lost the GPU. ``pp=1`` is bit-identical
        to the unstaged session (same step graph, same ledger types).

        ``allocator`` (a `repro.cluster.GreedyAllocator`) turns every
        replan into a GLOBAL search — spares assignable to any stage,
        cost-priced cross-stage swaps — and is what makes ``spares > 0``
        legal at pp > 1 (DESIGN.md §2.7). The session binds the allocator's
        goodput model to its own geometry and calibrates its transition cost
        model from the live packed trees, so predicted bytes match the
        executed `TransferStats` ledger exactly; the latest verdict is kept
        in ``session.last_global_plan``."""
        self = cls._new()
        self._backend = "ntp"
        self._cfg = cfg
        self._mesh = mesh
        self._mode = Mode.coerce(mode)
        self._local_batch = local_batch
        self._optimizer = optimizer or adamw(AdamWConfig(lr=1e-2))
        if power_policy is not None and self._mode is Mode.DP_DROP:
            raise ValueError(
                "a PowerPolicy decides NTP/NTP-PW batches — contradictory "
                "with Mode.DP_DROP (which zeroes degraded replicas)"
            )
        self._policy = power_policy
        self._spares = spares
        from repro.core.overlap import coerce_overlap

        self._overlap = coerce_overlap(overlap)
        self._decision = None
        self._quarantine = quarantine
        self._quarantined = ()
        self._snapshot = None
        self.last_transition = None   # TransferStats of the latest repack
        self.last_global_plan = None  # allocator's latest GlobalPlan verdict
        self.last_rollback = False    # latest apply() rolled back to snapshot
        d, n1 = mesh.shape["data"], mesh.shape["model"]
        if "stage" in getattr(mesh, "axis_names", ()):
            # measured submesh PP (core/pp_submesh, DESIGN.md §2.8): one
            # device slice per pipeline stage, validated before any compute
            from repro.core.pp_submesh import validate_staged_mesh

            validate_staged_mesh(mesh, pp)

        if allocator is not None and pp <= 1:
            raise ValueError(
                "allocator= is the pp>1 global repack planner; pp=1 sessions "
                "already pack globally (plan_from_health handles spares)"
            )
        self._allocator = allocator
        if allocator is not None:
            from repro.cluster import GoodputModel
            from repro.core.policies import WorkloadGeometry
            from repro.core.power import PowerModel

            method = ("ntp_pw" if power_policy is not None
                      and power_policy.name == "ntp_pw" else "ntp")
            allocator.bind(goodput=GoodputModel(
                n1=n1,
                geom=WorkloadGeometry(n_heads=cfg.n_kv_groups,
                                      local_batch=local_batch),
                method=method,
                power=(power_policy.model if power_policy is not None
                       else PowerModel()),
            ))

        if pp < 1:
            raise ValueError(f"pp must be >= 1, got {pp}")
        if isinstance(plan, StagedPlan) and plan.pp == 1:
            plan = plan.stages[0]
        if isinstance(health, StagedHealth) and health.pp == 1:
            health = health.stages[0]
        for given, what in ((plan, "plan"), (health, "health")):
            given_pp = getattr(given, "pp", None)
            if given_pp is not None and pp != 1 and given_pp != pp:
                raise ValueError(
                    f"{what} has {given_pp} stages but pp={pp} was requested"
                )
            if given_pp is not None:
                pp = given_pp
            elif given is not None and pp != 1:
                # a plain FailurePlan/ClusterHealth is ambiguous under pp>1
                # (per-stage state differs by stage); broadcasting failures
                # to every stage would silently change their blast radius
                raise ValueError(
                    f"pp={pp} needs a staged {what} "
                    f"(StagedPlan/StagedHealth), got {type(given).__name__}"
                )
        self._pp = pp
        self._microbatches = microbatches
        if pp == 1:
            if health is None:
                health = (
                    ClusterHealth.from_plan(plan) if plan is not None
                    else ClusterHealth.pristine(d, n1)
                )
            self._health = health
            packed = plan_from_health(health, spares=spares)
            if plan is not None and plan != packed:
                # a plan out of packed order would make replica-addressed
                # events resolve against the wrong physical domain
                raise ValueError(
                    f"plan {plan} is not in resource-manager packed order "
                    f"(most-degraded first); health {health.failed} packs to "
                    f"{packed}"
                )
        else:
            if health is None:
                health = (
                    StagedHealth.from_plan(as_staged(plan))
                    if plan is not None
                    else StagedHealth.pristine(d, n1, pp)
                )
            self._health = health
            packed = self._staged_replan(health, current=None)
            if plan is not None and as_staged(plan) != packed:
                raise ValueError(
                    f"staged plan {plan} is not in per-stage packed order "
                    f"(most-degraded first per stage); health packs to "
                    f"{packed}"
                )
        self._plan = packed
        assert self._plan.d == d and self._plan.n1 == n1, (
            f"plan {self._plan} does not fit mesh (data={d}, model={n1})"
        )
        if pp > 1:
            from repro.configs.shapes import stage_boundaries

            self._boundaries = stage_boundaries(cfg.n_layers, pp)

        canonical = params if params is not None else nt.init_canonical(
            cfg, key if key is not None else jax.random.PRNGKey(0)
        )
        self._params = nt.pack_params(cfg, canonical, self._plan)
        self._opt = self._optimizer.init(self._params)
        self._place_state()
        if self._allocator is not None:
            # calibrate move pricing from the LIVE trees: predicted bytes of
            # a candidate transition then equal the executed TransferStats
            # ledger exactly (params + every param-like optimizer tree ride
            # the same fused buckets)
            from repro.cluster import TransitionCostModel

            opt_keys = [k for k in self._optimizer.param_like
                        if k in self._opt]
            trees = [self._params] + [self._opt[k] for k in opt_keys]
            self._allocator.bind(cost=TransitionCostModel.from_trees(
                cfg, jax.device_get(trees), pp=pp))
        self._events: List[LifecycleEvent] = []
        self._last_metrics: Dict[str, Any] = {}
        self._decide()
        self._build_step()
        return self

    @classmethod
    def from_arch(
        cls,
        cfg,                    # repro.configs.base.ArchConfig
        shape,                  # repro.configs.shapes.ShapeSpec (kind="train")
        mesh=None,
        *,
        opt_cfg: Optional[AdamWConfig] = None,
        param_dtype=jnp.float32,
        lr_schedule=None,
        key=None,
    ) -> "NTPSession":
        """Uniform session over the production arch stack (make_setup)."""
        import functools

        from repro.optim import warmup_cosine
        from repro.train.steps import make_setup

        self = cls._new()
        self._backend = "arch"
        self._cfg = cfg
        self._mesh = mesh
        self._mode = Mode.UNIFORM
        kw = {}
        if lr_schedule is not None:
            kw["lr_schedule"] = lr_schedule
        self._setup = make_setup(cfg, shape, mesh, param_dtype=param_dtype,
                                 opt_cfg=opt_cfg, **kw)
        self._step_fn = self._setup.jit_step()
        key = key if key is not None else jax.random.PRNGKey(0)
        from repro.optim import adamw_init

        if mesh is not None:
            self._params = jax.jit(
                self._setup.model.init, out_shardings=self._setup.param_sharding
            )(key)
            self._opt = jax.jit(
                lambda p: adamw_init(p, self._setup.opt_cfg),
                out_shardings=self._setup.opt_sharding,
            )(self._params)
        else:
            self._params = self._setup.model.init(key)
            self._opt = adamw_init(self._params, self._setup.opt_cfg)
        self._health = None
        self._plan = None
        self._events = []
        self._last_metrics = {}
        self._policy = None
        self._spares = 0
        self._overlap = False
        self._pp = 1
        self._microbatches = 1
        self._decision = None
        self._stage_rel = None
        self._allocator = None
        self._quarantine = False
        self._quarantined = ()
        self._snapshot = None
        self.last_transition = None
        self.last_global_plan = None
        self.last_rollback = False
        return self

    # ------------------------------------------------------------- introspect

    @property
    def backend(self) -> str:
        """``"ntp"`` (NTPSession.create, full lifecycle surface) or
        ``"arch"`` (NTPSession.from_arch, uniform training only — lifecycle
        calls raise NotImplementedError naming the alternative)."""
        return self._backend

    @property
    def mode(self) -> Mode:
        return self._mode

    @property
    def plan(self) -> Optional[Union[FailurePlan, StagedPlan]]:
        """The live plan: a `FailurePlan` for pp=1 (exactly as before stages
        existed), a `StagedPlan` for pp > 1."""
        return self._plan

    @property
    def health(self) -> Optional[Union[ClusterHealth, StagedHealth]]:
        return self._health

    @property
    def pp(self) -> int:
        return self._pp

    @property
    def overlap(self) -> bool:
        """Whether the step runs the overlapped, bucketed gradient sync
        (core/overlap, DESIGN.md §2.10)."""
        return self._overlap

    @property
    def stage_boundaries(self):
        """Layer boundaries of the pipeline stages (pp+1 ints; pp>1 only)."""
        return self._boundaries if self._pp > 1 else (0, self._cfg.n_layers)

    @property
    def events(self) -> List[LifecycleEvent]:
        return list(self._events)

    @property
    def cfg(self):
        return self._cfg

    @property
    def optimizer(self) -> Optimizer:
        return self._optimizer

    @property
    def local_batch(self) -> int:
        return self._local_batch

    @property
    def local_batches(self):
        """Per-replica usable samples under the current plan (the power
        policy's decision, or the mode's default rule; quarantined replicas
        contribute 0 — DESIGN.md §2.11)."""
        self._require_ntp("local batch accounting")
        if self._decision is not None:
            return list(self._decision.local_batches)
        lbs = list(
            nt.default_local_batches(self._plan, self._mode, self._local_batch)
        )
        for r in self._quarantined:
            lbs[r] = 0
        return lbs

    @property
    def quarantine(self) -> bool:
        """Whether SDC suspicions quarantine their replica and roll back to
        the latest `snapshot()` (DESIGN.md §2.11). Off: SDC events are
        recorded but priced as healthy."""
        return self._quarantine

    @property
    def quarantined(self):
        """Replica indices currently quarantined by an open SDC suspicion
        (empty when quarantine is off or no suspicion is open)."""
        return tuple(self._quarantined)

    @property
    def power_decision(self):
        """The PowerPolicy's verdict for the current plan (None: no policy)."""
        return self._decision

    @property
    def params(self):
        """The live (packed / sharded) parameter tree."""
        return self._params

    @property
    def opt_state(self):
        return self._opt

    @property
    def opt_step(self) -> int:
        return int(jax.device_get(self._opt["step"]))

    def canonical_params(self, replica: int = 0) -> Dict:
        """Dense canonical weights recovered from one replica (NTP backend)."""
        self._require_ntp("canonical weight reconstruction")
        return nt.unpack_params(self._cfg, jax.device_get(self._params),
                                self._plan, replica=replica)

    # ---------------------------------------------------------------- train

    def step(self, batch) -> Dict[str, Any]:
        """One optimizer step; returns the metrics dict (loss, grad_norm, …).
        Under a PowerPolicy the dict additionally carries the policy verdict:
        ``policy``, ``power_boost`` (max ×TDP over replicas) and the
        predicted ``rel_iter_time``.

        The step is wrapped in a ``session.step`` span inside a profiler
        step marker (``StepTraceAnnotation("train", step_num=...)``, counted
        on the host); the first step after the step function was (re)built
        carries ``compiled=True``, since it pays for the trace and compile.
        NOTE the span times DISPATCH (jax is async; nothing here blocks on
        device work — that would change recorder-off numerics' timing);
        wall-per-step lives in the orchestrator/bench spans that own the
        `block_until_ready`."""
        tel = telemetry.get()
        with jax.profiler.StepTraceAnnotation("train",
                                              step_num=self._host_step), \
                tel.span("session.step", backend=self._backend, pp=self._pp,
                         overlap="on" if self._overlap else "off") as sp:
            if self._fresh_step:
                sp.set(compiled=True)
            self._params, self._opt, metrics = self._step_fn(
                self._params, self._opt, batch
            )
        self._fresh_step = False
        self._host_step += 1
        if tel.enabled and self._decision is not None:
            tel.gauge("train.rel_iter_time", self._decision.rel_iter_time,
                      source="analytic", policy=self._decision.method)
        if self._decision is not None:
            metrics = dict(
                metrics,
                policy=self._decision.method,
                power_boost=self._decision.max_boost,
                rel_iter_time=self._decision.rel_iter_time,
            )
        if self._stage_rel is not None:
            # staged sessions always predict per-stage relative iteration
            # time (slowest stage gates the replica — perf_model's
            # staged_iteration_time rule), policy or not
            metrics = dict(
                metrics,
                stage_rel_iter_time=self._stage_rel,
                rel_iter_time=max(self._stage_rel),
            )
        if getattr(self._step_fn, "submesh", False):
            # the measured submesh path annotates its pipeline schedule: the
            # tick count behind the bubble and the per-step cross-stage
            # hand-off byte table (core/pp_submesh.handoff_accounting)
            metrics = dict(
                metrics,
                pipeline_ticks=self._step_fn.ticks,
                handoff=self._step_fn.handoff,
            )
        self._last_metrics = metrics
        return metrics

    def measure_sync(self, batch) -> Dict[str, Any]:
        """Measure the step's gradient sync in ISOLATION (the probe behind
        `BENCH_train.json`'s overlap rows and `launch/profile.py
        --measure`): run the step's ``grads_fn`` once to materialize a
        gradients tree, then execute ``sync_fn`` to completion under a
        ``train.sync`` telemetry span — phase marks ``issued`` /
        ``completed``, attrs ``collectives`` (static launch count),
        ``sync_s`` (blocking wall seconds) and ``exposed_s``. Measured in
        isolation the sync is fully exposed (``exposed_s == sync_s``); how
        much of it the overlapped step actually hides is the STEP-level
        difference bench_hotpath computes from the on/off pair, and
        `perf_model.exposed_comm` predicts from the overlappable-compute
        window. Returns the attrs dict. NTP backend only."""
        self._require_ntp("sync measurement")
        step = self._step_fn
        if not hasattr(step, "sync_fn"):
            raise NotImplementedError(
                "this step builder carries no sync probe")
        import time

        tel = telemetry.get()
        _, grads = step.grads_fn(self._params, batch)
        jax.block_until_ready(grads)
        label = "on" if getattr(step, "overlap", False) else "off"
        with tel.span("train.sync", overlap=label,
                      backend=self._backend) as sp:
            sp.mark("issued")
            t0 = time.perf_counter()
            out = step.sync_fn(grads)
            jax.block_until_ready(out)
            sync_s = time.perf_counter() - t0
            sp.mark("completed")
            attrs = {"collectives": int(step.collectives),
                     "sync_s": sync_s, "exposed_s": sync_s}
            sp.set(**attrs)
        return dict(attrs, overlap=label)

    # ---------------------------------------------------------------- events

    def apply(self, event: LifecycleEvent):
        """Consume a lifecycle event: update health, replan, and repack
        params and optimizer state into the new plan — training continues
        with the same logical weights. For a `FailureEvent` that is the
        paper's restart minus the restart (TP goes down); for a
        `RecoveryEvent` it is the missing inverse (TP comes back up, params
        and AdamW state spread back over the repaired ranks).

        On a staged (pp > 1) session the event resolves to ONE pipeline
        stage (`StagedHealth.resolve_site`); only that stage's layer slice
        repacks — stage-local `transition_trees`, zero cross-stage traffic.
        Returns the new plan (`FailurePlan` for pp=1, `StagedPlan` else).

        With telemetry active the whole replan+repack is one
        ``session.transition`` span: phase marks ``planned``, then, when
        state moves, ``gathered`` (state on the host), ``repacked``,
        ``placed`` (the repacked state on the devices: the span waits for
        it) and ``executed``, with the executed `TransferStats` ledger
        attached as attributes — the span's byte counts equal
        ``last_transition`` exactly (the Perfetto trace carries the same
        numbers the tests assert against). The new step program is traced
        and compiled by the next `step`, whose span says ``compiled``."""
        self._require_ntp("lifecycle replanning")

        tel = telemetry.get()
        self.last_rollback = False
        with tel.span(
            "session.transition",
            kind=event_kind(event),
            pp=self._pp,
        ) as sp:
            new_health = self._health.apply(event)
            if self._pp == 1:
                new_plan = plan_from_health(new_health, spares=self._spares)
            else:
                new_plan = self._staged_replan(new_health, current=self._plan)
            sp.mark("planned")
            self._events.append(event)
            self._health = new_health
            if new_plan == self._plan:
                if isinstance(event, DEGRADATION_EVENTS):
                    # the TP plan is untouched (degradation never removes a
                    # GPU) but the decision surface moved: batches shrink on
                    # straggle/link, SDC quarantines, boosts re-aim
                    before = tuple(self.local_batches)
                    old_mode = self._mode
                    if self._mode is Mode.UNIFORM and not new_health.healthy:
                        self._mode = Mode.NTP
                    self._decide()
                    if (isinstance(event, SdcSuspectEvent)
                            and self._quarantine
                            and self._snapshot is not None):
                        self.rollback()
                    if (self._mode is not old_mode
                            or tuple(self.local_batches) != before):
                        self._build_step()
                    sp.set(changed=False, degraded=True,
                           rollback=self.last_rollback)
                    return self._plan
                sp.set(changed=False)
                return self._plan

            old_plan = self._plan
            self._transition(old_plan, new_plan, sp)
            sp.mark("executed")
            sp.set(changed=True, old_plan=str(old_plan),
                   new_plan=str(new_plan), **self.last_transition.as_dict())
            if tel.enabled:
                tel.gauge("cluster.transition_bytes",
                          self.last_transition.bytes_moved, source="executed")
            self._plan = new_plan
            if self._mode is Mode.UNIFORM and not new_plan.healthy:
                self._mode = Mode.NTP  # uniform degrades into NTP, not death
            self._decide()
            if (isinstance(event, SdcSuspectEvent) and self._quarantine
                    and self._snapshot is not None):
                self.rollback()
            self._build_step()
            return new_plan

    # ------------------------------------------------------------ checkpoint

    def save(self, path: str) -> None:
        """Write params + optimizer state in CANONICAL layout: restorable
        into a session running under any FailurePlan."""
        self._require_ntp("canonical checkpointing")
        tree = {
            "params": self.canonical_params(),
            "opt": self._canonical_opt(),
        }
        save_checkpoint(path, tree, step=self.opt_step)

    def restore(self, path: str) -> int:
        """Load a canonical checkpoint into the CURRENT plan's packing.
        Returns the saved step. Leaves restore at the dtype they were SAVED
        with (the checkpoint's recorded dtype wins over the live tree's —
        repro/checkpoint/checkpoint.py); cast after restore to convert."""
        self._require_ntp("canonical checkpointing")
        like = {
            "params": self.canonical_params(),
            "opt": self._canonical_opt(),
        }
        tree, step = load_checkpoint(path, like)
        self._params = nt.pack_params(self._cfg, tree["params"], self._plan)
        self._opt = self._pack_opt(tree["opt"])
        self._place_state()
        return step if step is not None else self.opt_step

    def snapshot(self) -> None:
        """Capture an in-memory canonical restore point — params AND
        optimizer state in the same layout `save` writes, minus the file.
        `rollback()` repacks it into whatever plan is live at rollback time,
        so the snapshot survives any number of fail/repair transitions in
        between (DESIGN.md §2.11: the quarantine rollback target)."""
        self._require_ntp("SDC rollback snapshots")
        self._snapshot = {
            "params": self.canonical_params(),
            "opt": self._canonical_opt(),
        }

    def rollback(self) -> int:
        """Restore the latest `snapshot()` into the CURRENT plan's packing —
        the checkpoint-free analogue of `restore`, used when an SDC
        suspicion quarantines a replica and its recent updates are
        untrusted. Returns the restored optimizer step. `apply()` invokes
        this automatically on `SdcSuspectEvent` when quarantine is on and a
        snapshot exists; ``session.last_rollback`` records that it fired."""
        self._require_ntp("SDC rollback snapshots")
        if self._snapshot is None:
            raise RuntimeError(
                "no restore point: call session.snapshot() before relying "
                "on SDC rollback"
            )
        self._params = nt.pack_params(
            self._cfg, self._snapshot["params"], self._plan
        )
        self._opt = self._pack_opt(self._snapshot["opt"])
        self._place_state()
        self.last_rollback = True
        return self.opt_step

    # ---------------------------------------------------------------- private

    def _require_ntp(self, what: str) -> None:
        """Guard for features the arch backend does not implement. The error
        names the caller that hit it (the public method, via the stack) and
        the ``what`` feature, so a trace replay or launcher flag that lands
        here is diagnosable without reading this file."""
        if self._backend != "ntp":
            import inspect

            frame = inspect.stack()[1]
            raise NotImplementedError(
                f"NTPSession.{frame.function}() needs {what}, which only the "
                "NTP prototype backend implements — this session was built "
                "with NTPSession.from_arch() (uniform training via "
                "train/steps.make_setup; a failure there is a full restart). "
                "Build the session with NTPSession.create(...) — e.g. "
                "launch/train.py --ntp instead of --arch — to use lifecycle "
                "events, canonical checkpoints, or power policies."
            )

    def _place_state(self) -> None:
        """Commit the packed params and param-like optimizer trees to the
        mesh with the step's shard_map specs (unit buffers split over
        (data, model), the rest replicated). The first step after a
        (re)pack then sees the same input shardings as the steps after it,
        so it compiles one program, not two. Staged submesh meshes place
        their own stacked trees (core/pp_submesh); a mesh stand-in without
        devices holds nothing."""
        if (not isinstance(self._mesh, Mesh)
                or "stage" in self._mesh.axis_names):
            return

        def place(tree):
            return jax.device_put(tree, jax.tree.map(
                lambda spec: NamedSharding(self._mesh, spec),
                nt._tree_specs(tree)))

        replicated = NamedSharding(self._mesh, PartitionSpec())
        self._params = place(self._params)
        self._opt = {
            k: (place(v) if k in self._optimizer.param_like
                else jax.device_put(v, replicated))
            for k, v in self._opt.items()
        }

    def _staged_replan(self, health: StagedHealth, *, current):
        """One pp>1 replan: the global allocator when bound (joint spares /
        swap search, moves priced against ``current``'s in-place state —
        verdict kept in ``last_global_plan``), stage-local packing
        otherwise."""
        if self._allocator is not None:
            gp = self._allocator.plan(health, spares=self._spares,
                                      current=current)
            self.last_global_plan = gp
            return gp.staged_plan
        return staged_plan_from_health(health, spares=self._spares)

    def _replica_degradations(self):
        """Per-replica merged degradation ledgers of the current health, or
        None when the health carries none — the binary fail/repair path then
        passes ``degradations=None`` everywhere and every decision stays
        bit-identical to the pre-taxonomy sessions. SDC entries are masked
        out when quarantine is off (the operator opted out of trusting the
        detector), so only straggle/link pricing remains."""
        h = self._health
        if isinstance(h, StagedHealth):
            if all(st.degraded is None for st in h.stages):
                return None
        elif h.degraded is None:
            return None
        degs = h.replica_degradations()
        if not self._quarantine and any(d.sdc for d in degs):
            from dataclasses import replace

            degs = tuple(replace(d, sdc=0) for d in degs)
        return degs

    def _decide(self) -> None:
        """Consult the PowerPolicy (if any) for the current plan. Geometry is
        derived from the live model: attention quantizes at kv-group (unit)
        granularity. A staged plan decides on its `effective` (slowest-stage)
        reduction and additionally predicts per-stage relative iteration
        times for the step metrics. The health's degradation ledgers ride
        along (§2.11): stragglers/links reprice the slowdown, open SDC
        suspicions quarantine their replica (batch 0)."""
        from repro.core.policies import WorkloadGeometry

        self._stage_rel = None
        degs = self._replica_degradations()
        self._quarantined = (
            tuple(r for r, dg in enumerate(degs) if dg.sdc > 0)
            if degs is not None else ()
        )
        eff_plan = self._plan.effective if self._pp > 1 else self._plan
        geom = (self._policy.geom if self._policy is not None else None) or \
            WorkloadGeometry(
                n_heads=self._cfg.n_kv_groups, local_batch=self._local_batch
            )
        if self._policy is None:
            self._decision = None
        else:
            self._decision = self._policy.decide(
                eff_plan, local_batch=self._local_batch, geom=geom,
                degradations=degs,
            )
        if self._pp > 1:
            from repro.core.policies import staged_rel_iter_times
            from repro.core.power import PowerModel

            if self._decision is not None:
                boosts = self._decision.boost
                lbs = self._decision.local_batches
                power = self._policy.model
            else:
                boosts = None
                lbs = [int(b) for b in nt.default_local_batches(
                    eff_plan, self._mode, self._local_batch
                )]
                for r in self._quarantined:
                    lbs[r] = 0
                lbs = tuple(lbs)
                power = PowerModel()
            if degs is not None:
                slow_factors = tuple(dg.slow_factor for dg in degs)
                bw_fracs = tuple(dg.bw_frac for dg in degs)
            else:
                slow_factors = bw_fracs = None
            self._stage_rel = staged_rel_iter_times(
                self._plan.stage_tp, self._plan.n1, geom,
                local_batches=lbs, local_batch=self._local_batch,
                boosts=boosts, power=power,
                slow_factors=slow_factors, bw_fracs=bw_fracs,
            )

    def _build_step(self) -> None:
        if self._decision is not None:
            lbs = self._decision.local_batches
        elif self._quarantined:
            lbs = tuple(self.local_batches)
        else:
            lbs = None  # the builder's default rule — binary path unchanged
        self._fresh_step = True
        self._step_fn = nt.make_ntp_train_step(
            self._cfg, self._plan, self._mesh, mode=self._mode,
            local_batch=self._local_batch, optimizer=self._optimizer,
            local_batches=lbs,
            microbatches=self._microbatches,
            overlap=self._overlap,
        )

    def _transition(self, old, new, sp) -> None:
        """One fused packed→packed transition for params AND every
        param-like optimizer leaf tree (AdamW m/v/master): all of them ride
        the same per-(replica, src, dst) buckets, so the whole fail/repair
        move is one bucketed send per rank pair — O(moved units), not
        O(model), host traffic (repro.reshard.transition). For pp > 1 only
        the stages whose plan changed repack their layer slice; the session
        owns its trees exclusively, so untouched stages pass through with
        zero bytes and zero copies (``copy_unchanged=False``). The transfer
        accounting is kept in `last_transition`; ``sp`` (the
        ``session.transition`` span) gets the phase marks."""
        from repro.reshard.transition import (
            transition_staged_trees, transition_trees,
        )

        opt = jax.device_get(self._opt)
        opt_keys = [k for k in self._optimizer.param_like if k in opt]
        trees = [jax.device_get(self._params)] + [opt[k] for k in opt_keys]
        sp.mark("gathered")
        if self._pp == 1:
            moved, stats = transition_trees(self._cfg, trees, old, new)
        else:
            moved, stats = transition_staged_trees(
                self._cfg, trees, old, new, copy_unchanged=False
            )
        sp.mark("repacked")
        self._params = moved[0]
        self._opt = dict(opt, **dict(zip(opt_keys, moved[1:])))
        self._place_state()
        if telemetry.get().enabled:
            jax.block_until_ready((self._params, self._opt))
        sp.mark("placed")
        self.last_transition = stats

    def _canonical_opt(self) -> Dict:
        opt = jax.device_get(self._opt)
        return {
            k: (
                nt.unpack_params(self._cfg, v, self._plan)
                if k in self._optimizer.param_like else v
            )
            for k, v in opt.items()
        }

    def _pack_opt(self, canonical_opt: Dict) -> Dict:
        return {
            k: (
                nt.pack_params(self._cfg, v, self._plan)
                if k in self._optimizer.param_like else v
            )
            for k, v in canonical_opt.items()
        }
