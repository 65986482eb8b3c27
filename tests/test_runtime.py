"""Runtime API tests: event/health -> plan bridging, Mode dispatch, the
packed-tree repack algebra, and the lifecycle-orchestrator surface (policy
decisions + trace schedules) — all host-side; the live NTPSession lifecycle
runs in multi-device subprocesses (tests/dist/session_transition.py,
tests/dist/session_lifecycle.py)."""
import numpy as np
import pytest

import jax

from repro.core import ntp_train as nt
from repro.core.failure_model import FailureTraceConfig
from repro.core.nonuniform import FailurePlan
from repro.optim import AdamWConfig, adamw, sgd
from repro.runtime import (
    ClusterHealth, DeadReplicaError, FailureEvent, Mode, RecoveryEvent,
    plan_from_health, power_policy, resolve_serving_domain,
    schedule_from_trace,
)


# ---------------------------------------------------------------------------
# serving event addressing (ISSUE 4 satellite: validated ONCE, here)

def test_resolve_serving_domain_aliases_replica_one_to_one():
    ev = resolve_serving_domain(FailureEvent(replica=2, n_gpus=3), 4)
    assert isinstance(ev, FailureEvent)
    assert ev.domain == 2 and ev.replica is None and ev.n_gpus == 3
    rv = resolve_serving_domain(RecoveryEvent(domain=1), 4)
    assert isinstance(rv, RecoveryEvent) and rv.domain == 1


@pytest.mark.parametrize("bad", [-1, 4, 99])
@pytest.mark.parametrize("field", ["domain", "replica"])
def test_resolve_serving_domain_rejects_out_of_range(bad, field):
    ev = FailureEvent(**{field: bad})
    with pytest.raises(ValueError, match=rf"domain {bad}.*valid ids: 0\.\.3"):
        resolve_serving_domain(ev, 4)


# ---------------------------------------------------------------------------
# events / health / plan bridge

def test_pristine_health_gives_uniform_plan():
    h = ClusterHealth.pristine(4, 8)
    plan = plan_from_health(h)
    assert plan == FailurePlan(n1=8, replica_tp=(8, 8, 8, 8))
    assert plan.healthy and h.healthy


def test_plan_from_health_packs_failures_into_lowest_replicas():
    # failures scattered over domains 1 and 3 -> packed to replicas 0, 1
    h = ClusterHealth(domain_size=8, failed=(0, 2, 0, 1))
    plan = plan_from_health(h)
    assert plan.replica_tp == (6, 7, 8, 8)
    assert plan.n_sync == 6


def test_plan_from_health_round_trips_assignments():
    h = ClusterHealth(domain_size=4, failed=(1, 0))
    asg = h.assignments()
    plan = plan_from_health(h)
    assert tuple(a.tp for a in asg) == plan.replica_tp == (3, 4)
    # the degraded physical domain (id 0) sits at the lowest replica
    assert asg[0].domain_ids[0] == 0 and asg[0].failed[0] == 1


def test_plan_from_health_spares_absorb_failures():
    h = ClusterHealth(domain_size=8, failed=(3, 0, 1, 0))
    assert plan_from_health(h).replica_tp == (5, 7, 8, 8)
    assert plan_from_health(h, spares=1).replica_tp == (7, 8, 8, 8)
    assert plan_from_health(h, spares=2).replica_tp == (8, 8, 8, 8)


def test_dead_replica_raises():
    h = ClusterHealth(domain_size=4, failed=(4, 0))
    with pytest.raises(DeadReplicaError):
        plan_from_health(h)


def test_failure_event_validation():
    with pytest.raises(ValueError):
        FailureEvent()                       # neither address
    with pytest.raises(ValueError):
        FailureEvent(domain=0, replica=1)    # both addresses
    with pytest.raises(ValueError):
        FailureEvent(domain=0, n_gpus=0)


def test_health_apply_by_domain_and_replica():
    h = ClusterHealth(domain_size=4, failed=(0, 0))
    h1 = h.apply(FailureEvent(domain=1))
    assert h1.failed == (0, 1)
    # replica-addressed: replica 0 currently serves the degraded domain 1
    # (most-failed packs lowest), so the hit lands there again
    h2 = h1.apply(FailureEvent(replica=0))
    assert h2.failed == (0, 2)
    # saturates at domain_size
    h3 = h2.apply(FailureEvent(domain=1, n_gpus=99))
    assert h3.failed == (0, 4)


def test_health_from_plan_round_trip():
    plan = FailurePlan(n1=4, replica_tp=(3, 4))
    h = ClusterHealth.from_plan(plan)
    assert h.failed == (1, 0)
    assert plan_from_health(h) == plan


def test_recovery_event_validation_matches_failure_event():
    with pytest.raises(ValueError):
        RecoveryEvent()
    with pytest.raises(ValueError):
        RecoveryEvent(domain=0, replica=1)
    with pytest.raises(ValueError):
        RecoveryEvent(domain=0, n_gpus=0)


def test_health_recovery_by_domain_and_replica():
    h = ClusterHealth(domain_size=4, failed=(0, 2))
    # domain-addressed repair
    assert h.apply(RecoveryEvent(domain=1)).failed == (0, 1)
    # replica-addressed: replica 0 serves the degraded domain 1 (packed
    # lowest), so the repair lands there
    assert h.apply(RecoveryEvent(replica=0)).failed == (0, 1)
    # saturates at healthy; surplus repairs are no-ops
    assert h.apply(RecoveryEvent(domain=1, n_gpus=99)).failed == (0, 0)
    assert h.apply(RecoveryEvent(domain=0)).failed == (0, 2)


def test_fail_repair_cycle_restores_plan():
    h = ClusterHealth.pristine(2, 4)
    hurt = h.apply(FailureEvent(replica=1)).apply(FailureEvent(domain=0))
    assert plan_from_health(hurt).replica_tp == (3, 3)
    healed = hurt.apply(RecoveryEvent(domain=0)).apply(RecoveryEvent(domain=1))
    assert healed == h
    assert plan_from_health(healed).healthy


# ---------------------------------------------------------------------------
# power policy (the NTP vs NTP-PW decision hook)

def test_power_policy_table1_settings():
    """The policy's verdict at the paper's TP32 geometry must agree with
    table1_settings: TP30-PW keeps the full local batch within the 1.3× rack
    cap; plain TP30 sheds one sample."""
    plan = FailurePlan(n1=32, replica_tp=(30, 32))
    pw = power_policy("ntp_pw").decide(plan, local_batch=8)
    assert pw.method == "ntp_pw"
    assert pw.local_batches == (8, 8)
    assert 1.0 < pw.max_boost <= 1.3 + 1e-9
    assert pw.rel_iter_time <= 1.005

    ntp = power_policy("ntp").decide(plan, local_batch=8)
    assert ntp.method == "ntp"
    assert ntp.local_batches == (7, 8)
    assert ntp.max_boost == 1.0


def test_power_policy_beyond_cap_sheds_batch_but_never_below_ntp():
    """Past the rack cap the boosted replica sheds samples, but never ends
    up with fewer than the un-boosted ∝-TP share."""
    plan = FailurePlan(n1=4, replica_tp=(2, 4))
    pw = power_policy("ntp_pw").decide(plan, local_batch=4)
    ntp = power_policy("ntp").decide(plan, local_batch=4)
    assert pw.boost[0] == pytest.approx(1.3)
    assert ntp.local_batches[0] <= pw.local_batches[0] < 4


def test_power_policy_healthy_plan_is_uniform():
    d = power_policy("ntp_pw").decide(FailurePlan(n1=4, replica_tp=(4, 4)),
                                      local_batch=4)
    assert d.method == "uniform"
    assert d.boost == (1.0, 1.0) and d.rel_iter_time == 1.0


def test_power_policy_rejects_unknown_name():
    with pytest.raises(ValueError):
        power_policy("dvfs")


# ---------------------------------------------------------------------------
# trace -> schedule bridge

def test_schedule_from_trace_pairs_and_bounds():
    cfg = FailureTraceConfig(n_gpus=8, domain_size=4, days=40.0,
                             rate_multiplier=2000.0, seed=1,
                             hw_recovery_days=(0.2, 0.4),
                             sw_recovery_hours=2.0)
    steps = 400
    sched = schedule_from_trace(cfg, steps=steps)
    assert sched, "expected events at this rate"
    assert all(0 <= s.step < steps for s in sched)
    assert all(s.event.domain in (0, 1) for s in sched)
    assert [s.step for s in sched] == sorted(s.step for s in sched)
    fails = sum(1 for s in sched if not isinstance(s.event, RecoveryEvent))
    repairs = len(sched) - fails
    # every repair matches an in-window failure; tail failures may be unhealed
    assert 0 < repairs <= fails
    # same-step tiebreak: repairs first (a repair can legalize a failure)
    for a, b in zip(sched, sched[1:]):
        if a.step == b.step:
            assert not (isinstance(a.event, FailureEvent)
                        and isinstance(b.event, RecoveryEvent)), (a, b)
    # replaying against the ledger never under/overflows
    h = ClusterHealth.pristine(2, 4)
    for s in sched:
        h = h.apply(s.event)
        assert all(0 <= f <= 4 for f in h.failed)


class _LedgerSession:
    """Duck-typed stand-in for NTPSession: just the health/plan ledger, so
    TraceRunner's event-application semantics test without a mesh."""

    def __init__(self, d, n1):
        self.health = ClusterHealth.pristine(d, n1)
        self.plan = plan_from_health(self.health)

    def apply(self, event):
        new_health = self.health.apply(event)
        new_plan = plan_from_health(new_health)  # may raise DeadReplicaError
        self.health, self.plan = new_health, new_plan
        return new_plan


def test_trace_runner_absorbs_repairs_of_rejected_failures():
    """A failure rejected with DeadReplicaError never touched the ledger, so
    its paired repair must be absorbed — NOT applied — or the replayed TP
    trajectory overstates surviving capacity."""
    from repro.runtime import ScheduledEvent, TraceRunner

    session = _LedgerSession(2, 2)
    runner = TraceRunner(session, [
        ScheduledEvent(0, FailureEvent(step=0, domain=0)),    # tp (1, 2)
        ScheduledEvent(1, FailureEvent(step=1, domain=0)),    # tp 0: rejected
        ScheduledEvent(2, RecoveryEvent(step=2, domain=0)),   # pair of the
        ScheduledEvent(3, RecoveryEvent(step=3, domain=0)),   # rejected: absorb
    ])
    runner._apply_due(0)
    assert session.plan.replica_tp == (1, 2)
    runner._apply_due(1)
    assert session.plan.replica_tp == (1, 2)          # rejected, unmutated
    assert runner.transitions[-1]["kind"] == "rejected"
    runner._apply_due(2)
    # the dead GPU's repair heals the REAL failure; the orphaned repair of
    # the rejected event (step 3) must then be a pure no-op
    assert session.health.failed == (0, 0) or session.health.failed == (1, 0)
    runner._apply_due(3)
    assert session.health.failed == (0, 0)
    assert session.plan.healthy
    kinds = [t["kind"] for t in runner.transitions]
    assert kinds.count("rejected") == 1 and kinds.count("absorbed") == 1, kinds


# ---------------------------------------------------------------------------
# Mode enum dispatch

def test_mode_coerce_accepts_legacy_strings():
    assert Mode.coerce("uniform") is Mode.UNIFORM
    assert Mode.coerce("ntp") is Mode.NTP
    assert Mode.coerce("dpdrop") is Mode.DP_DROP
    assert Mode.coerce("dp_drop") is Mode.DP_DROP
    assert Mode.coerce(Mode.NTP) is Mode.NTP
    with pytest.raises(ValueError):
        Mode.coerce("bogus")


def _tiny_cfg():
    return nt.NTPModelConfig(d_model=32, n_kv_groups=2, q_per_kv=1,
                             head_dim=16, d_ff=64, unit_rows=32,
                             n_layers=1, vocab=64)


def test_mode_dispatch_local_batches():
    """UNIFORM keeps full local batches, NTP reduces ∝ TP, DP_DROP zeroes
    the degraded replica — the observable Mode semantics."""
    plan = FailurePlan(n1=2, replica_tp=(1, 2))
    lb = 4
    assert list(plan.local_batch_fraction(lb)) == [2, 4]           # NTP
    dropped = [lb if t == plan.n1 else 0 for t in plan.replica_tp]
    assert dropped == [0, 4]                                       # DP_DROP


# ---------------------------------------------------------------------------
# repack algebra (host-side; no mesh needed)

def test_repack_params_matches_manual_unpack_pack():
    cfg = _tiny_cfg()
    old = FailurePlan(n1=2, replica_tp=(2, 2))
    new = FailurePlan(n1=2, replica_tp=(1, 2))
    canon = nt.init_canonical(cfg, jax.random.PRNGKey(0))
    packed = nt.pack_params(cfg, canon, old)

    got = nt.repack_params(cfg, packed, old, new)
    want = nt.pack_params(cfg, nt.unpack_params(cfg, packed, old), new)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))

    # canonical content is preserved across the transition, from any replica
    for r in range(new.d):
        back = nt.unpack_params(cfg, got, new, replica=r)
        for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(canon)):
            assert np.allclose(np.asarray(a), np.asarray(b))


def test_repack_is_noop_for_same_plan():
    cfg = _tiny_cfg()
    plan = FailurePlan(n1=2, replica_tp=(1, 2))
    packed = nt.pack_params(cfg, nt.init_canonical(cfg, jax.random.PRNGKey(1)),
                            plan)
    assert nt.repack_params(cfg, packed, plan, plan) is packed


def test_optimizer_state_trees_are_repackable():
    """AdamW moments mirror the param structure, so the same repack applies
    (what NTPSession.apply relies on); SGD has no param-like state."""
    cfg = _tiny_cfg()
    old = FailurePlan(n1=2, replica_tp=(2, 2))
    new = FailurePlan(n1=2, replica_tp=(1, 2))
    packed = nt.pack_params(cfg, nt.init_canonical(cfg, jax.random.PRNGKey(2)),
                            old)
    opt = adamw(AdamWConfig(lr=1e-3))
    state = opt.init(packed)
    assert set(opt.param_like) >= {"m", "v"}
    for k in ("m", "v"):
        re = nt.repack_params(cfg, state[k], old, new)
        want = nt.pack_params(cfg, nt.unpack_params(cfg, state[k], old), new)
        for a, b in zip(jax.tree.leaves(re), jax.tree.leaves(want)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    assert sgd(1e-2).param_like == ()


def test_session_rejects_unpacked_plan_order():
    """A plan out of resource-manager packed order would mis-resolve
    replica-addressed events; create() must reject it before any compute."""
    from repro.runtime import NTPSession

    class StubMesh:  # only .shape is read before validation
        shape = {"data": 2, "model": 4}

    with pytest.raises(ValueError, match="packed order"):
        NTPSession.create(_tiny_cfg(), StubMesh(),
                          plan=FailurePlan(n1=4, replica_tp=(4, 3)))


def test_require_ntp_names_caller_and_alternative():
    """ISSUE 7 satellite: the arch backend's guard must name the public entry
    point that hit it (via the call stack), the missing feature, and the
    supported alternative (`--ntp instead of --arch`) — and the ntp backend
    must pass the same guard silently."""
    from conftest import reduced_cfg
    from repro.configs.shapes import ShapeSpec
    from repro.runtime import NTPSession

    arch = NTPSession.from_arch(reduced_cfg("qwen2-7b"),
                                ShapeSpec("t", 16, 2, "train"), None)
    assert arch.backend == "arch"
    with pytest.raises(NotImplementedError,
                       match=r"NTPSession\.canonical_params\(\) needs "
                             r"canonical weight reconstruction"):
        arch.canonical_params()
    with pytest.raises(NotImplementedError,
                       match=r"NTPSession\.apply\(\) needs lifecycle "
                             r"replanning.*--ntp instead of --arch"):
        arch.apply(FailureEvent(replica=0, n_gpus=1))
    with pytest.raises(NotImplementedError, match=r"NTPSession\.save\(\)"):
        arch.save("/nonexistent/never-written")

    class StubMesh:
        axis_names = ("data", "model")
        shape = {"data": 2, "model": 2}

    ntp = NTPSession.create(_tiny_cfg(), StubMesh(),
                            plan=FailurePlan(n1=2, replica_tp=(2, 2)))
    assert ntp.backend == "ntp"
    assert ntp.local_batches == [4, 4]          # guard passes, no raise
    canon = ntp.canonical_params()
    assert set(canon) >= {"embed", "head", "layers"}


@pytest.mark.parametrize("optimizer", [sgd(0.05), adamw(AdamWConfig(lr=1e-3))],
                         ids=["sgd", "adamw"])
def test_session_step_compiles_once_across_repacks(optimizer):
    """The packed state is committed with the step's shardings after init
    and after every repack, so the step compiles once: the second step,
    and the first step after a rollback repack, compile nothing. Without
    that placement each repack costs a second compile of the step."""
    import jax.numpy as jnp
    from repro.runtime import NTPSession

    cfg = _tiny_cfg()
    compiles = []

    def listener(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    session = NTPSession.create(cfg, jax.make_mesh((1, 1), ("data", "model")),
                                local_batch=2, optimizer=optimizer,
                                key=jax.random.PRNGKey(0))
    batch = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab, (2, 9)), jnp.int32)

    def step_compiles():
        n = len(compiles)
        jax.block_until_ready(session.step(batch))
        return len(compiles) - n

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        step_compiles()
        session.snapshot()
        assert step_compiles() == 0
        session.rollback()
        assert step_compiles() == 0
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


# ---------------------------------------------------------------------------
# live session transition (8 fake devices, subprocess)

@pytest.mark.slow
def test_session_failure_transition(run_dist):
    """healthy -> degraded via NTPSession.apply: params + AdamW state match
    the manual unpack/repack path exactly, training continues, and the
    canonical checkpoint round-trips."""
    out = run_dist("session_transition.py")
    assert "SESSION_TRANSITION_OK" in out


@pytest.mark.slow
def test_session_adamw_matches_canonical(run_dist):
    """AdamW on packed buffers (incl. global-norm clipping via the 1/D
    norm_weights correction) == canonical AdamW."""
    out = run_dist("ntp_adamw_equivalence.py")
    assert "NTP_ADAMW_OK" in out


@pytest.mark.slow
def test_session_pp1_bit_identical(run_dist):
    """ISSUE 5 acceptance: the stage-aware session at pp=1 is BIT-identical
    to the pre-PR NTPSession across a random fail/repair chain (params,
    AdamW state, per-step metrics)."""
    out = run_dist("session_pp1_regression.py")
    assert "SESSION_PP1_REGRESSION_OK" in out


@pytest.mark.slow
def test_session_pp_lifecycle(run_dist):
    """ISSUE 5 acceptance: pp=2 with one stage at reduced TP matches the
    dense reference through fail->repair; transitions are stage-local; the
    per-stage rel_iter_time metrics follow the slowest-stage rule."""
    out = run_dist("session_pp_lifecycle.py")
    assert "SESSION_PP_LIFECYCLE_OK" in out


@pytest.mark.slow
def test_session_submesh_pp_measured(run_dist):
    """ISSUE 7 acceptance: the measured submesh pipeline (per-stage device
    slices + ppermute hand-off, core/pp_submesh) matches the stage-sequential
    emulation step-for-step through fail->repair, and its hand-off byte
    table equals the independent accounting."""
    out = run_dist("session_submesh_pp.py", devices=16)
    assert "SESSION_SUBMESH_PP_OK" in out


@pytest.mark.slow
def test_allocator_pp_spares_lifecycle(run_dist):
    """ISSUE 6 acceptance: pp=2 with ONE spare domain, stage-addressed
    fail->repair chain — the allocator-driven session matches the dense
    reference to f32 exactness, the spare absorbs / relocates across stages,
    and `session.last_transition` carries only the allocator's priced moves
    (predicted bytes == executed ledger, no dense round-trip)."""
    out = run_dist("session_allocator_lifecycle.py")
    assert "SESSION_ALLOC_PP_OK" in out
