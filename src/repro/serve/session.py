"""ServeSession — the serving façade parallel to `runtime.NTPSession`
(DESIGN.md §2.5).

One session owns D serving replicas (one `ServeEngine` each, sharing one
weight copy), the per-domain failed-GPU ledger (`runtime.events.ClusterHealth`),
and the fault-tolerance policy deciding what a degraded replica does:

* ``drop``   — the baseline: any failure kills the whole replica (all
  in-flight requests preempted, cache lost); it returns only when its
  domain is fully repaired. The serving twin of training DP_DROP.
* ``ntp``    — the replica keeps serving at reduced TP: per-request state
  (KV cache and/or SSM/rgLRU recurrent state) resharded in place through
  the unified engine (`repro.reshard.ShardedState`), decode slowed by the
  unit-quantized `stage_slowdown`, slot pool shrunk ∝ surviving ranks.
* ``ntp_pw`` — NTP plus the paper's §3.2 power boost: survivors run up to
  the rack cap (`policies.boosted_operating_point`), erasing most or all of
  the slowdown at full slot shrinkage only.

Unlike training, serving replicas are NOT repacked across domains on
failure (`plan_from_health` is a job-wide re-shuffle; a serving replica is
pinned to its domain by the KV state living there), so events address
domains and replica ``r`` simply serves domain ``r``; `apply(event)`
reshards weights (re-derived per-rank head layout — weights are stateless,
the KV cache is what must physically move), KV cache, and slot map in
place and hands back whatever was preempted.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.configs.base import ArchConfig
from repro.core.nonuniform import FailurePlan
from repro.core.policies import WorkloadGeometry
from repro.core.power import PowerModel
from repro.models.transformer import build_model
from repro.runtime.events import ClusterHealth, LifecycleEvent
from repro.serve.engine import Request, ServeEngine
from repro.serve.router import replica_serve_speed

SERVE_POLICIES = ("drop", "ntp", "ntp_pw")


class ServeSession:
    """Stateful serving session: D engines + health ledger + policy."""

    def __init__(self, *_, **__):
        raise TypeError("use ServeSession.create(...)")

    @classmethod
    def create(
        cls,
        cfg: ArchConfig,
        *,
        replicas: int = 1,
        n1: int = 4,
        slots: int = 8,
        max_len: int = 96,
        prefill_len: int = 32,
        policy: str = "ntp",
        power_model: PowerModel = PowerModel(),
        geom: Optional[WorkloadGeometry] = None,
        dtype=jnp.float32,
        params=None,
        key=None,
        use_kernel: bool = False,
        quarantine: bool = True,
    ) -> "ServeSession":
        if policy not in SERVE_POLICIES:
            raise ValueError(f"policy {policy!r} not in {SERVE_POLICIES}")
        from repro.serve.engine import validate_serve_cfg

        validate_serve_cfg(cfg)
        self = object.__new__(cls)
        self._cfg = cfg
        self._policy = policy
        self._power = power_model
        # decode quantizes at the model's COARSEST partition-unit family
        # (KV heads / SSD heads / rgLRU blocks — reshard.units), with the
        # analytic model's decode-time FLOP split — same blend as
        # SERVE_GEOM, only the unit count comes from the live model
        from dataclasses import replace as _replace

        from repro.reshard.units import serve_unit_count
        from repro.serve.router import SERVE_GEOM

        self._geom = geom or _replace(
            SERVE_GEOM, n_heads=serve_unit_count(cfg), local_batch=slots
        )
        model = build_model(cfg, remat=False)
        if params is None:
            params = model.init(key if key is not None else jax.random.PRNGKey(0))
        self._params = params
        self._health = ClusterHealth.pristine(replicas, n1)
        self._n1 = n1
        self._dtype = dtype
        # one model + one jit cache for every replica: the programs are
        # identical, only the (shared) params and per-engine caches differ
        compiled = (jax.jit(model.decode_slots), jax.jit(model.prefill),
                    jax.jit(model.decode_step))
        self.engines = [
            ServeEngine(cfg, params, n1=n1, slots=slots, max_len=max_len,
                        prefill_len=prefill_len, dtype=dtype,
                        use_kernel=use_kernel, model=model, compiled=compiled)
            for _ in range(replicas)
        ]
        self._events: List[LifecycleEvent] = []
        self._repair_debt: Dict[int, int] = {}   # domain -> clamp surplus
        self._quarantine = quarantine
        self.transitions: List[Dict] = []
        return self

    # ------------------------------------------------------------ introspect

    @property
    def cfg(self) -> ArchConfig:
        return self._cfg

    @property
    def params(self):
        return self._params

    @property
    def policy(self) -> str:
        return self._policy

    @property
    def quarantine(self) -> bool:
        """Whether an open SDC suspicion drains its replica (§2.11)."""
        return self._quarantine

    @property
    def health(self) -> ClusterHealth:
        return self._health

    @property
    def events(self) -> List[LifecycleEvent]:
        return list(self._events)

    @property
    def replica_tp(self) -> Tuple[int, ...]:
        """Surviving TP degree per (domain-pinned) serving replica."""
        return tuple(self._n1 - f for f in self._health.failed)

    @property
    def plan(self) -> Optional[FailurePlan]:
        """The session's health as a `FailurePlan` (None while any replica
        is fully dead — FailurePlan has no TP-0 representation)."""
        tp = self.replica_tp
        if any(t < 1 for t in tp):
            return None
        return FailurePlan(n1=self._n1, replica_tp=tp)

    def total_rate(self) -> float:
        """Upper-bound decode tokens per wall tick across live replicas."""
        return float(sum(e.rel_speed * e.capacity for e in self.engines))

    # ---------------------------------------------------------------- events

    def _operating_point(self, tp: int, deg=None) -> Tuple[int, float, float]:
        """(engine_tp, rel_speed, power_boost) the policy assigns to a
        replica whose domain has ``tp`` surviving GPUs — the SAME ladder and
        FLOP blend the analytic model pins (`router.replica_serve_speed`);
        only the head count is the live model's. ``deg`` is the domain's
        `DomainDegradation` ledger (§2.11): stragglers / degraded links slow
        the replica instead of dropping it."""
        speed, boost = replica_serve_speed(
            tp, self._n1, self._policy, geom=self._geom, power=self._power,
            slow_factor=deg.slow_factor if deg is not None else 1.0,
            bw_frac=deg.bw_frac if deg is not None else 1.0,
        )
        if speed == 0.0:  # tp 0, or drop policy with any failure: dead
            return 0, 0.0, 1.0
        return tp, speed, boost

    def apply(self, event: LifecycleEvent) -> List[Request]:
        """Consume a lifecycle event: update the ledger, retarget every
        affected engine (KV reshard / death / revival + speed + slot map),
        and return the preempted requests for the router to requeue.

        Failures beyond a domain's size clamp in the ledger but leave a
        per-domain repair DEBT, and the matching surplus repairs of the
        clamped trace are absorbed against it (the serving twin of
        `orchestrator.TraceRunner`'s debt) — otherwise a fully-dead replica
        would revive while its trace still has every GPU down, inflating
        live goodput relative to the analytic replay of the same trace.

        Degradation events (§2.11) DRAIN-THEN-RETARGET instead of dropping:
        the replica's TP (and with it cache layout + slot pool) never
        changes, so nothing is preempted — its decode rate is repriced
        through the degradation ledger, and an open SDC suspicion puts the
        engine in ``draining`` (in-flight requests finish, no new admits,
        the router routes around it) until the clear."""
        from repro.runtime.events import (
            DEGRADATION_EVENTS, RecoveryEvent, resolve_serving_domain,
        )

        # domain-pinned addressing (replica= aliases domain 1:1) is
        # validated/normalized ONCE, in runtime.events
        event = resolve_serving_domain(event, self._health.n_domains)
        dom = event.domain
        if isinstance(event, DEGRADATION_EVENTS):
            return self._apply_degradation(event, dom)
        if isinstance(event, RecoveryEvent):
            debt = self._repair_debt.get(dom, 0)
            absorbed = min(debt, event.n_gpus)
            if absorbed:
                self._repair_debt[dom] = debt - absorbed
                if absorbed == event.n_gpus:
                    self.transitions.append({
                        "event": event, "replica": dom, "kind": "absorbed",
                        "tp_from": self.replica_tp[dom],
                        "tp_to": self.replica_tp[dom], "preempted": 0,
                    })
                    return []
                event = RecoveryEvent(step=event.step, domain=dom,
                                      n_gpus=event.n_gpus - absorbed)
        else:
            overflow = self._health.failed[dom] + event.n_gpus - self._n1
            if overflow > 0:
                self._repair_debt[dom] = (
                    self._repair_debt.get(dom, 0) + overflow
                )
        old_tp = self.replica_tp
        self._health = self._health.apply(event)
        self._events.append(event)
        degs = self._health.replica_degradations()
        preempted: List[Request] = []
        tel = telemetry.get()
        from repro.runtime.events import event_kind

        with tel.span(
            "serve.transition",
            kind=event_kind(event),
            policy=self._policy,
        ) as sp:
            reshard_bytes = 0
            for r, engine in enumerate(self.engines):
                tp, speed, boost = self._operating_point(
                    self.replica_tp[r], degs[r]
                )
                if tp == engine.tp and not (engine.dead and tp > 0):
                    engine.rel_speed, engine.power_boost = speed, boost
                    continue
                pre = engine.apply_tp(tp, rel_speed=speed, power_boost=boost)
                preempted += pre
                reshard_bytes += engine.last_reshard.get("bytes_moved", 0)
                self.transitions.append({
                    "event": event, "replica": r,
                    "tp_from": old_tp[r], "tp_to": tp,
                    "preempted": len(pre),
                    "power_boost": boost, "rel_speed": speed,
                    "reshard": dict(engine.last_reshard),
                })
            sp.set(domain=dom, preempted=len(preempted),
                   bytes_moved=reshard_bytes)
            if tel.enabled and preempted:
                tel.counter("serve.preempted", len(preempted),
                            policy=self._policy)
        return preempted

    def _apply_degradation(self, event, dom: int) -> List[Request]:
        """Drain-then-retarget (§2.11): the TP plan, cache layout and slot
        pool are untouched (degradation never removes a GPU), so NOTHING is
        preempted — every live engine's decode rate is repriced through the
        updated ledger, and an open SDC suspicion flips the engine to
        ``draining`` until its clear. Returns [] (the `apply` contract's
        preempted list — always empty here)."""
        from repro.runtime.events import event_kind

        self._health = self._health.apply(event)
        self._events.append(event)
        degs = self._health.replica_degradations()
        tel = telemetry.get()
        with tel.span(
            "serve.transition", kind=event_kind(event), policy=self._policy,
        ) as sp:
            for r, engine in enumerate(self.engines):
                if engine.dead:
                    continue
                _, speed, boost = self._operating_point(
                    self.replica_tp[r], degs[r]
                )
                draining = self._quarantine and degs[r].sdc > 0
                changed = (speed != engine.rel_speed
                           or boost != engine.power_boost
                           or draining != engine.draining)
                engine.rel_speed, engine.power_boost = speed, boost
                engine.draining = draining
                if changed:
                    self.transitions.append({
                        "event": event, "replica": r, "kind": "retarget",
                        "tp_from": engine.tp, "tp_to": engine.tp,
                        "preempted": 0, "power_boost": boost,
                        "rel_speed": speed, "draining": draining,
                    })
            sp.set(domain=dom, preempted=0)
        return []

    # ------------------------------------------------------------------ run

    def tick(self) -> List[Request]:
        """One wall tick on every live engine; returns finished requests."""
        done: List[Request] = []
        for e in self.engines:
            done += e.tick()
        return done

    # ------------------------------------------------------------ checkpoint

    def _engine_state(self, e) -> Dict:
        """One engine's KV-bearing state as fixed-shape arrays: dense cache,
        slot tables, and the in-flight REQUEST BODIES (prompt + generated
        prefix, padded to max_len) — without these a restored session would
        have live slots pointing at requests it cannot name."""
        ml = e.max_len
        prompt = np.zeros((e.slots, ml), np.int32)
        p_len = np.zeros(e.slots, np.int32)
        gen = np.zeros((e.slots, ml), np.int32)
        g_len = np.zeros(e.slots, np.int32)
        max_new = np.zeros(e.slots, np.int32)
        for b in np.flatnonzero(e._rid >= 0):
            req = e._req[int(e._rid[b])]
            prompt[b, : len(req.prompt)] = req.prompt
            p_len[b] = len(req.prompt)
            gen[b, : len(req.generated)] = req.generated
            g_len[b] = len(req.generated)
            max_new[b] = req.max_new
        return {
            "kv": e.cache,
            "rid": np.asarray(e._rid, np.int32),
            "pos": np.asarray(e._pos, np.int32),
            "cur_tok": np.asarray(e._cur_tok, np.int32),
            "admit_order": np.asarray(e._admit_order, np.int32),
            "admitted": np.asarray(e._admitted, np.int32),
            "req_prompt": prompt, "req_prompt_len": p_len,
            "req_gen": gen, "req_gen_len": g_len, "req_max_new": max_new,
        }

    def save(self, path: str) -> None:
        """Write the KV-bearing state: weights + every replica's DENSE
        (layout-independent) cache + slot tables + in-flight request bodies.
        Cache dtypes (bf16 serving caches) round-trip via the checkpoint
        dtype records. The router's queue/accounting are not persisted —
        queued requests were never admitted, so resubmitting them is safe."""
        save_checkpoint(
            path,
            {"params": self._params,
             "engines": [self._engine_state(e) for e in self.engines]},
        )

    def restore(self, path: str) -> List[Request]:
        """Load a `save` checkpoint into the CURRENT per-replica layouts
        (a checkpoint taken under any TP restores under any other — the
        dense cache is canonical, like training's canonical weights).
        In-flight requests are rebuilt and decoding continues where it
        stopped; arrival/deadline are session-clock-relative and reset.
        The target session's OWN in-flight requests are preempted first and
        returned along with any checkpointed slots beyond a degraded
        replica's CURRENT capacity (the same preempt-and-return invariant
        `apply` enforces) — nothing is silently dropped."""
        like = {"params": self._params,
                "engines": [self._engine_state(e) for e in self.engines]}
        tree, _ = load_checkpoint(path, like)
        preempted: List[Request] = []
        for e in self.engines:
            while e.n_active:
                preempted.append(e._preempt_one())
        self._params = tree["params"]
        for r, e in enumerate(self.engines):
            st = tree["engines"][r]
            e.params = self._params
            e._cache = st["kv"]
            for k, attr in (("rid", "_rid"), ("pos", "_pos"),
                            ("cur_tok", "_cur_tok"),
                            ("admit_order", "_admit_order")):
                setattr(e, attr, np.asarray(st[k]).astype(np.int64))
            e._admitted = int(st["admitted"])
            e._req = {}
            for b in np.flatnonzero(e._rid >= 0):
                p_len = int(st["req_prompt_len"][b])
                g_len = int(st["req_gen_len"][b])
                req = Request(
                    rid=int(e._rid[b]),
                    prompt=np.asarray(st["req_prompt"][b][:p_len], np.int32),
                    max_new=int(st["req_max_new"][b]),
                    generated=[int(t) for t in st["req_gen"][b][:g_len]],
                )
                e._req[req.rid] = req
            while e.n_active > e.capacity:
                preempted.append(e._preempt_one())
        return preempted
