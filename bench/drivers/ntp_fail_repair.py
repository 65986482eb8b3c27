"""Closed-loop NTP training through one failure and one repair inside the
timed window: ``NTPSession.create`` on a data x model mesh (the
``launch/train.py --ntp`` path), batches from ``SyntheticLMPipeline``,
AdamW, float32 weights made from the seed and handed to the session as
canonical parameters.

Traffic parameters: ``data`` and ``model`` (the mesh), ``local_batch``
(rows a replica a step), ``seq``, ``in_flight`` (steps the host may run
ahead of the device between transitions), ``fail_at`` and ``repair_at``
(shares of the window after which the first step boundary takes
``FailureEvent(replica=1)`` and the ``RecoveryEvent`` that undoes it; the
repair waits until ``min_degraded_steps`` steps have run degraded),
``check_steps``, ``check_fail_step``, ``check_repair_step`` (the set-up's
check cycle) and ``ref_rows`` (rows the reference takes at a time).

A replica at TP t of n trains on floor(local_batch * t / n) of its rows
(the paper's local-batch reduction); the tokens of a step are those rows
times ``seq``. Set-up drives the session through the check cycle, its
failure and repair included, through the window's own call and feed, and
keeps each step's loss, the first gradient's norms (from AdamW's first
moment after it) and the norms of the canonical parameters' change after
the last. That also builds the programs of both layouts. The window goes
on with the same session. Before each event the host waits for the steps
in flight; the event's transition and the first step in the new layout,
which builds its program, are timed apart. The window closes at
``seconds``, or after the repair's first step where that comes later, so
a slow transition lengthens the window and never drops the repair. Once the window has closed and
the session is freed, the reference repeats the check cycle on the same
tokens and rows from the same weights.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import sys
import time
from collections import deque
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from bench import harness  # noqa: E402
from bench import reference as R  # noqa: E402
from bench.drivers.train_closed_loop import compare  # noqa: E402
from bench.harness import Check, Outcome, span  # noqa: E402

CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
PHASES = ("planned", "gathered", "repacked", "placed", "executed")


class CacheHits:
    """Persistent-cache hits over the process, from JAX's own event. A
    listener cannot be taken away again, so there is one for the process."""

    n = 0
    _listening = False

    @classmethod
    def listen(cls):
        import jax

        if cls._listening:
            return

        def event(name, **_):
            if name == CACHE_HIT_EVENT:
                cls.n += 1

        jax.monitoring.register_event_listener(event)
        cls._listening = True


def programs(run):
    """(programs built, persistent-cache hits) so far: the run's compile
    meter counts every compile-or-load of a program."""
    return run.meter.snapshot()[1], CacheHits.n


def usable_rows(replica_tp, n1: int, local_batch: int):
    """Rows of each replica's local batch that train: floor(local_batch *
    t / n1) for a replica at TP t of n1."""
    return [local_batch * t // n1 for t in replica_tp]


def row_weight(replica_tp, n1: int, local_batch: int) -> np.ndarray:
    """1 for each row of the global batch that trains (replica r holds rows
    r*local_batch to (r+1)*local_batch - 1 and trains on the first of
    them), 0 for the rest."""
    w = np.zeros(len(replica_tp) * local_batch, np.float32)
    for r, u in enumerate(usable_rows(replica_tp, n1, local_batch)):
        w[r * local_batch: r * local_batch + u] = 1.0
    return w


def events():
    from repro.runtime import FailureEvent, RecoveryEvent

    # after the failure the degraded replica is packed first (most degraded
    # first), so the repair names replica 0
    return FailureEvent(replica=1), RecoveryEvent(replica=0)


def expected_plans(tr):
    healthy = (tr["model"],) * tr["data"]
    degraded = (tr["model"] - 1,) + (tr["model"],) * (tr["data"] - 1)
    return [healthy, degraded, healthy]


def named(tree, prefix: str = ""):
    """(dotted leaf name, leaf) of a nested dict/list, the names
    ``reference.named_norms`` gives."""
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from named(tree[k], f"{prefix}.{k}" if prefix else str(k))
    elif isinstance(tree, (list, tuple)):
        for i, x in enumerate(tree):
            yield from named(x, f"{prefix}.{i}" if prefix else str(i))
    else:
        yield prefix, tree


def host_norms(tree):
    return {k: float(np.linalg.norm(np.asarray(v, np.float32).reshape(-1)))
            for k, v in named(tree)}


# ------------------------------------------------------------- set-up

def setup(run):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.data.pipeline import DataConfig, SyntheticLMPipeline
    from repro.optim import AdamWConfig, adamw, warmup_cosine
    from repro.runtime import NTPSession

    s, tr, C = run.cell.sizes, run.cell.traffic, run.cell.config
    o = s["optimizer"]
    sch = o["schedule"]
    cfg = C.model_config(s)
    mesh = jax.make_mesh((tr["data"], tr["model"]), ("data", "model"),
                         devices=run.devices)
    opt = adamw(AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                            weight_decay=o["weight_decay"],
                            grad_clip=o["grad_clip"]),
                lr_schedule=functools.partial(
                    warmup_cosine, warmup=sch["warmup"], total=sch["total"],
                    floor=sch["floor"]))
    key = jax.random.PRNGKey(harness.seed32(run.seed, 1))
    # made on the device in one call; the session packs canonical weights
    # on the host, so they go there, and the device holds no second copy
    w0 = jax.device_get(jax.jit(lambda k: C.init(s, k))(key))
    session = NTPSession.create(cfg, mesh, local_batch=tr["local_batch"],
                                optimizer=opt, params=w0)
    pipe = SyntheticLMPipeline(DataConfig(
        s["vocab_size"], tr["seq"], tr["data"] * tr["local_batch"],
        seed=run.seed))
    rows = NamedSharding(mesh, PartitionSpec("data", None))
    return {"session": session, "pipe": pipe, "cfg": cfg, "w0": w0,
            "feed": lambda i: jax.device_put(pipe._batch_np(i), rows)}


def check_steps(run, st):
    """The check cycle, through the window's own call and feed."""
    import jax

    from repro.core import ntp_train as nt

    s, tr = run.cell.sizes, run.cell.traffic
    session, cfg = st["session"], st["cfg"]
    fail, repair = events()
    at = {tr["check_fail_step"]: fail, tr["check_repair_step"]: repair}
    b1 = s["optimizer"]["b1"]
    rec = {"tokens": [], "plans": [], "loss": []}
    for i in range(tr["check_steps"]):
        if i in at:
            session.apply(at[i])
        rec["tokens"].append(st["pipe"]._batch_np(i))
        rec["plans"].append(tuple(session.plan.replica_tp))
        metrics = session.step(st["feed"](i))
        rec["loss"].append(float(metrics["loss"]))
        if i == 0:
            # unpacked onto one device: freed before the failure's
            # transition, which stages the whole new state there
            m = nt.unpack_params(cfg, jax.device_get(session.opt_state["m"]),
                                 session.plan)
            rec["grad"] = {k: v / (1 - b1) for k, v in host_norms(m).items()}
            del m
    now = session.canonical_params()
    rec["change"] = host_norms(jax.tree.map(
        lambda a, b: np.asarray(a) - b, now, st["w0"]))
    del now
    st.pop("w0")
    healthy, degraded, _ = expected_plans(tr)
    want = [degraded if tr["check_fail_step"] <= k < tr["check_repair_step"]
            else healthy for k in range(tr["check_steps"])]
    if rec["plans"] != want:
        raise RuntimeError(f"check cycle ran plans {rec['plans']}, not {want}")
    st["next_step"] = tr["check_steps"]
    return rec


# ------------------------------------------------------------- window

def transition(run, st, ev, i, t0, pauses):
    """One event in the window: its transition, then the first step in the
    new layout (which builds its program), waited for. The steps in flight
    have been waited for before."""
    import jax

    from repro import telemetry

    session = st["session"]
    b = st["feed"](i)
    p0 = programs(run)
    te = time.perf_counter()
    with span("bench.transition"), pauses.part("transition"):
        plan = session.apply(ev)
    ta = time.perf_counter()
    with span("bench.step"), pauses.part("first step"):
        metrics = session.step(b)
        jax.block_until_ready(metrics["loss"])
    tt = time.perf_counter()
    p1 = programs(run)
    sp = telemetry.get().spans("session.transition")[-1]
    return {"kind": type(ev).__name__, "plan": tuple(plan.replica_tp),
            "t_event": te - t0, "t_applied": ta - t0,
            "t_first_step": tt - t0, "span_s": sp["dur"],
            "marks": dict(sp["attrs"].get("marks", {})),
            "bytes_moved": int(session.last_transition.bytes_moved),
            "programs": p1[0] - p0[0],
            "compiles": (p1[0] - p0[0]) - (p1[1] - p0[1])}


def window(run, st):
    import jax

    tr = run.cell.traffic
    session = st["session"]
    lb, seq, n1 = tr["local_batch"], tr["seq"], tr["model"]
    todo = list(zip((tr["fail_at"], tr["repair_at"]), events()))
    pending = deque()
    steps = []          # (replica_tp, tokens, first in a new layout)
    trans = []
    i, since = st["next_step"], 0
    pauses = harness.HostPauses()
    p0 = programs(run)
    with run.traced():
        t0 = time.perf_counter()
        while (now := time.perf_counter() - t0) < run.seconds or todo:
            if (todo and now >= todo[0][0] * run.seconds
                    and (not trans or since >= tr["min_degraded_steps"])):
                with span("bench.wait"), pauses.part("drain"):
                    jax.block_until_ready(list(pending))
                pending.clear()
                trans.append(transition(run, st, todo.pop(0)[1], i, t0,
                                        pauses))
                tp = trans[-1]["plan"]
                steps.append((tp, sum(usable_rows(tp, n1, lb)) * seq, True))
                i, since = i + 1, 0
                continue
            with span("bench.batch"), pauses.part("batch"):
                b = st["feed"](i)
            tp = tuple(session.plan.replica_tp)
            with span("bench.step"), pauses.part("step"):
                metrics = session.step(b)
            pending.append(metrics["loss"])
            steps.append((tp, sum(usable_rows(tp, n1, lb)) * seq, False))
            i, since = i + 1, since + 1
            if len(pending) > tr["in_flight"]:
                with span("bench.wait"), pauses.part("wait"):
                    jax.block_until_ready(pending.popleft())
        with span("bench.wait"):
            jax.block_until_ready((session.params, list(pending)))
        t1 = time.perf_counter()
    p1 = programs(run)
    plans = [tp for k, (tp, _, _) in enumerate(steps)
             if k == 0 or tp != steps[k - 1][0]]
    if plans != expected_plans(tr) or len(trans) != 2:
        raise RuntimeError(f"the window ran plans {plans} through "
                           f"{len(trans)} transitions, not "
                           f"{expected_plans(tr)}")
    return {"steps": len(steps), "tokens": sum(t for _, t, _ in steps),
            "window_s": t1 - t0, "steps_run": steps, "transitions": trans,
            "programs": p1[0] - p0[0],
            "compiles": (p1[0] - p0[0]) - (p1[1] - p0[1]),
            "host": pauses.close()}


def phase_rates(win):
    """Tokens per second of the healthy steps before the failure (from the
    window's start to the wait before the event) and of the degraded steps
    after the first (from its end to the wait before the repair); the
    steps that follow a transition are left out of both."""
    fail, repair = win["transitions"]
    healthy = degraded = 0
    phase = 0
    for tp, tokens, first in win["steps_run"]:
        if first:
            phase += 1
            continue
        if phase == 0:
            healthy += tokens
        elif phase == 1:
            degraded += tokens
    dt_d = repair["t_event"] - fail["t_first_step"]
    return (healthy / fail["t_event"] if fail["t_event"] > 0 else None,
            degraded / dt_d if degraded and dt_d > 0 else None)


# ---------------------------------------------------------- reference

def reference_step(run, dtype):
    """The reference's training step, ``(w, state, tokens, row_weight, t)
    -> (w, state, loss, norms of the gradient as the moments take it)``:
    rows go ``ref_rows`` at a time and their gradients add up; the
    weights and the state are donated."""
    import jax
    import jax.numpy as jnp

    C, s = run.cell.config, run.cell.sizes
    opt = C.optimizer(s)
    blk = run.cell.traffic["ref_rows"]
    cast = lambda t: jax.tree.map(lambda x: x.astype(dtype), t)

    def step(w, state, tok, wt, t):
        def body(acc, xs):
            l, g = jax.value_and_grad(
                lambda w: C.loss_total(s, w, xs[0], xs[1], dtype))(w)
            return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

        n = tok.shape[0] // blk
        (total, g), _ = jax.lax.scan(
            body, (jnp.float32(0), jax.tree.map(jnp.zeros_like, w)),
            (tok.reshape(n, blk, -1), wt.reshape(n, blk)))
        count = jnp.sum(wt) * (tok.shape[1] - 1)
        g = jax.tree.map(lambda x: (x / count).astype(dtype), g)
        w2, state2, clipped = opt(w, state, g, t)
        return cast(w2), cast(state2), total / count, \
            C.reference_norms(clipped)

    return jax.jit(step, donate_argnums=(0, 1))


def reference(run, rec, *, dtype=None):
    """The reference's check numbers on the same tokens and rows, from the
    same weights, in float32 at ``highest`` matmul precision; or, for the
    control, everything in ``dtype`` at the precision the configuration
    states: weights, activations, gradients, the optimizer's state and
    each update."""
    import jax
    import jax.numpy as jnp

    C, s, tr = run.cell.config, run.cell.sizes, run.cell.traffic
    dtype = dtype or jnp.float32
    key = jax.random.PRNGKey(harness.seed32(run.seed, 1))
    cast = lambda t: jax.tree.map(lambda x: x.astype(dtype), t)
    prec = "highest" if dtype == jnp.float32 else s["matmul_precision"]
    with jax.default_matmul_precision(prec):
        w_init = jax.jit(lambda k: cast(C.init(s, k)))(key)
        w = jax.tree.map(lambda x: x + 0, w_init)
        state = jax.jit(lambda w: cast(R.adamw_init(w)))(w)
        step = reference_step(run, dtype)
        out = {"loss": []}
        for t, (tok, tp) in enumerate(zip(rec["tokens"], rec["plans"])):
            wt = row_weight(tp, tr["model"], tr["local_batch"])
            w, state, l, gn = step(w, state, tok, wt, t)
            out["loss"].append(float(l))
            if t == 0:
                out["grad"] = {k: float(v) for k, v in
                               jax.device_get(gn).items()}
        diff = jax.jit(lambda a, b: C.reference_norms(
            jax.tree.map(lambda x, y: x.astype(jnp.float32)
                         - y.astype(jnp.float32), a, b)))(w, w_init)
        out["change"] = {k: float(v) for k, v in jax.device_get(diff).items()}
    return out


def readings(run, rec, ref):
    """The control against the reference."""
    import jax.numpy as jnp

    return {"control_bf16": compare(reference(run, rec, dtype=jnp.bfloat16),
                                    ref)}


# ------------------------------------------------------- planted faults

@contextlib.contextmanager
def _patched(module, name, make):
    orig = getattr(module, name)
    setattr(module, name, make(orig))
    try:
        yield
    finally:
        setattr(module, name, orig)


def state_unchanged():
    """A planted fault: the step hands back its parameters and AdamW's
    moments as it got them (the step counter alone moves)."""
    import jax.numpy as jnp

    import repro.optim.base as B

    def make(orig):
        def frozen(grads, state, params, cfg, lr_scale=1.0,
                   norm_weights=None):
            return params, dict(state, step=state["step"] + 1), {
                "grad_norm": jnp.float32(0), "lr": jnp.float32(0)}
        return frozen

    return _patched(B, "adamw_update", make)


def half_batch():
    """A planted fault: each replica's loss leaves out the second half of
    its rows, the mean taken over the rest."""
    import jax.numpy as jnp

    from repro.core import ntp_train as nt

    def make(orig):
        def half(cfg, params, tokens, sample_mask, *a, **kw):
            keep = jnp.arange(tokens.shape[0]) < tokens.shape[0] // 2
            return orig(cfg, params, tokens,
                        sample_mask * keep.astype(sample_mask.dtype), *a, **kw)
        return half

    return _patched(nt, "_forward_local", make)


def no_exchange():
    """A planted fault: the gradient exchange between the replicas is left
    out (each replica's unit gradients stay its own)."""
    import repro.core.overlap as ov

    def make(orig):
        def none(*a, **kw):
            sync = orig(*a, **kw)
            ident = lambda grads: grads
            ident.collectives = sync.collectives
            return ident
        return none

    return _patched(ov, "make_sync_grads", make)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "no_exchange": no_exchange}


# ------------------------------------------------------------- a run

def describe(win) -> str:
    out = []
    for t in win["transitions"]:
        m = t["marks"]
        prev, parts = 0.0, []
        for ph in PHASES:
            if ph in m:
                parts.append(f"{ph} {m[ph] - prev:.3f}")
                prev = m[ph]
        out.append(f"{t['kind']} -> {t['plan']} at {t['t_event']:.3f} s: "
                   f"{', '.join(parts)}; span {t['span_s']:.3f} s; first "
                   f"step {t['t_first_step'] - t['t_applied']:.3f} s "
                   f"({t['programs']} programs, {t['compiles']} compiled); "
                   f"{t['bytes_moved']} B moved")
    return "; ".join(out)


def run(run):
    """One run; with ``seconds`` 0 there is no window (a reading of the
    check alone, as ``bench/readings.py`` takes it)."""
    from repro import telemetry

    lim = harness.load_limits(run)
    CacheHits.listen()
    with telemetry.recording(telemetry.Recorder(
            sinks=[telemetry.MemorySink()])):
        st = setup(run)
        rec = check_steps(run, st)
        run.setup_done()
        win = window(run, st) if run.seconds > 0 else None
    mem = harness.memory_peak_bytes(run.devices)
    st.clear()
    gc.collect()
    ref = reference(run, rec)
    nums = compare(rec, ref)
    checks = [Check(k, v, lim[k]) for k, v in nums.items()]
    e2e = {"setup_s": run.setup_s}
    if win is not None:
        e2e["ntp_goodput_tokens_per_s"] = win["tokens"] / win["window_s"]
        healthy, degraded = phase_rates(win)
        print(f"ntp window: {win['steps']} steps, {win['tokens']} tokens in "
              f"{win['window_s']:.3f} s; healthy {healthy} tokens/s, "
              f"degraded {degraded} tokens/s; {win['programs']} programs "
              f"built, {win['compiles']} compiled; " + describe(win) + "; "
              + harness.HostPauses.describe(win["host"]), file=sys.stderr)
    records = {"window": win, "check": {"program": rec, "reference": ref},
               "setup_compile_s": run.setup_compile_s}
    if run.readings:
        records["readings"] = readings(run, rec, ref)
    return Outcome(attempted=win["steps"] if win else 0, failed=0,
                   end_to_end=e2e, records=records, checks=checks,
                   memory_peak_bytes=mem)
