"""BENCH_hotpath: MEASURED wall-clock trajectory of the train/serve hot paths
(ISSUE 7 tentpole — extends the BENCH_*.json series started by bench_cluster).

Train rows come from a 16-fake-device subprocess that runs the SAME plan
through both pp=2 step builders and times them (`time.perf_counter` around
`block_until_ready`, after a compile warmup):

  * the stage-sequential emulation (`core.ntp_train._make_staged_train_step`)
  * the measured submesh pipeline (`core.pp_submesh` — per-stage device
    slices, ppermute hand-off, tick-scheduled 1F1B)

On serialized fake CPU devices every stage computes every tick, so the
submesh/emulation wall ratio IS the pipeline-bubble inflation — the measured
twin of `perf_model.staged_iteration_time`'s ``pp_bubble`` term, whose
analytic factor is ``(m + pp - 1) / m``. The two must agree within
``BUBBLE_REL_TOL`` (documented in DESIGN.md §2.8: CPU dispatch overhead and
the where-gated logits put a ceiling on how tight this can be). The
cross-stage hand-off byte table the submesh step reports is recorded next to
the reshard transition ledger of a stage failure on the same session.

Overlap rows (ISSUE 9) run the SAME degraded emulated pp=2 plan with the
overlapped bucketed gradient sync (`core.overlap`, DESIGN.md §2.10) off and
on, interleaved, plus `NTPSession.measure_sync` probes of each compiled
sync. On serialized fake devices nothing truly overlaps, so the model
prediction degenerates to the launch-collapse identity
``t_on ≈ (t_off − sync_off) + sync_on`` and the measured exposed comm must
match `perf_model.exposed_comm(sync_on, window=0) = sync_on` — both gated
at ``OVERLAP_REL_TOL``. A full (non-smoke) run additionally requires
overlap-on to be strictly faster than off (the bucketed sync launches far
fewer collectives, which is exactly what CPU dispatch overhead prices).

Kernel rows time each Pallas kernel interpret-vs-compiled
(`kernels.mode.pallas_interpret` resolution); on a CPU-only host the
compiled column carries an explicit ``"skipped": "no accelerator"`` note —
the ratio is only meaningful where the backend lowers Pallas.

Usage:
  python -m benchmarks.bench_hotpath            # measure, append BENCH_*.json
  python -m benchmarks.bench_hotpath --smoke    # quick run + schema check
  (also a `run()` module for benchmarks/run.py CSV rows)

``--smoke`` additionally validates the COMMITTED BENCH_train.json /
BENCH_serve.json against the schema this code produces and exits nonzero on
key drift — that is the CI `bench-smoke` job's contract.
"""
import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
TRAIN_PATH = os.path.join(REPO, "BENCH_train.json")
SERVE_PATH = os.path.join(REPO, "BENCH_serve.json")

# measured submesh/emulation wall ratio vs the analytic bubble factor
# (m+pp-1)/m: documented tolerance (DESIGN.md §2.8). Serialized-CPU dispatch
# overhead and the SPMD where-gated loss ticks both inflate the measured
# ratio, so this is loose by design; on a real multi-host accelerator the
# same contract should hold at a much tighter bound.
BUBBLE_REL_TOL = 0.40

# overlap-on step time vs the launch-collapse prediction
# t_on ≈ (t_off − sync_off) + sync_on: documented tolerance (DESIGN.md
# §2.10). Same caveats as the bubble gate — serialized-CPU dispatch noise
# on ~ms quantities keeps this loose; the identity itself is exact.
OVERLAP_REL_TOL = 0.35

# schema keys the CI bench-smoke job pins (drift = hard failure)
TRAIN_KEYS = {"config", "step_wall_ms", "bubble", "handoff", "kernels",
              "overlap"}
SERVE_KEYS = {"config", "prefill_and_decode", "kv_reshard"}


def _worker(smoke: bool) -> dict:
    """Runs inside the 16-fake-device subprocess; returns the measurements.

    All timings flow through one `repro.telemetry` recorder (spans around
    the block_until_ready'd regions, gauges for derived factors) and the
    report is read back from its MemorySink series — the bench consumes the
    same observability surface the runtime emits, instead of bespoke timer
    lists. The recorder is ACTIVE for the whole worker, so the runtime's own
    events (session spans, `kernels.dispatch` counters) land in the same
    ring and the kernel rows can cross-check their dispatch modes."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from repro import telemetry
    from repro.core import perf_model as pm
    from repro.kernels import ops
    from repro.launch.mesh import make_staged_mesh
    from repro.optim import sgd
    from repro.runtime import FailureEvent, NTPModelConfig, NTPSession
    from repro.telemetry import MemorySink, Recorder

    rec = Recorder(sinks=[MemorySink()])
    with telemetry.recording(rec):
        return _worker_recorded(smoke, rec, np, jax, jnp, pm, ops,
                                make_staged_mesh, sgd, FailureEvent,
                                NTPModelConfig, NTPSession)


def _worker_recorded(smoke, rec, np, jax, jnp, pm, ops, make_staged_mesh,
                     sgd, FailureEvent, NTPModelConfig, NTPSession) -> dict:
    LB, SEQ, MB = (4, 16, 2) if smoke else (8, 32, 4)
    # 6 smoke steps, not 2: the bubble gate estimates from per-step PAIRS,
    # and a 2-sample estimate is one scheduler hiccup away from the
    # tolerance edge; compile time dominates smoke wall time anyway
    steps = 6 if smoke else 5
    PP, D, N1 = 2, 2, 4
    cfg = NTPModelConfig(d_model=64, n_kv_groups=4, q_per_kv=2, head_dim=16,
                         d_ff=256, unit_rows=64, n_layers=4, vocab=128)

    # --- train: emulation vs submesh, same plan, same batches --------------
    mesh_emu = jax.make_mesh((D, N1), ("data", "model"))
    mesh_sub = make_staged_mesh(PP, D, N1)
    kw = dict(local_batch=LB, optimizer=sgd(0.05), key=jax.random.PRNGKey(0),
              pp=PP, microbatches=MB)
    emu = NTPSession.create(cfg, mesh_emu, **kw)
    sub = NTPSession.create(cfg, mesh_sub, **kw)
    rng = np.random.default_rng(0)

    def batch():
        return jnp.asarray(rng.integers(0, cfg.vocab, (D * LB, SEQ + 1)))

    def warmup(sess):
        # TWO warmup steps: the first compiles the fresh-params graph, the
        # second recompiles for the donated-buffer layout the steady state
        # actually runs with
        for _ in range(2):
            m = sess.step(batch())
            jax.block_until_ready((sess.params, m["loss"]))

    def one_step(sess, run):
        # the span closes only after block_until_ready, so its duration is
        # the step's true wall time, not its dispatch
        b = batch()
        with rec.span("bench.step", run=run):
            m = sess.step(b)
            jax.block_until_ready((sess.params, m["loss"]))
        return m

    def med_ms(run):
        return 1e3 * float(np.median(
            [s["dur"] for s in rec.spans("bench.step", run=run)]))

    # emulation and submesh steps INTERLEAVE so slow drifts in host load
    # land on both sides of the bubble ratio instead of biasing one loop
    warmup(emu)
    warmup(sub)
    for _ in range(steps):
        one_step(emu, "emulation")
        ms = one_step(sub, "submesh")
    t_emu, t_sub = med_ms("emulation"), med_ms("submesh")
    handoff = dict(ms["handoff"])
    # the bubble gate estimates the factor as the MEDIAN OF PER-PAIR RATIOS
    # from the interleaved steps: load transients within one pair hit both
    # numerator and denominator, and the median discards pairs where a
    # spike hit only one side — far more stable on a shared CPU host than
    # the ratio of two small-sample medians
    pair_ratios = [
        s["dur"] / e["dur"] for e, s in zip(
            rec.spans("bench.step", run="emulation"),
            rec.spans("bench.step", run="submesh"))
    ]

    # degraded stage still runs the measured path; its repack is the ledger
    sub.apply(FailureEvent(step=steps + 1, stage=1, domain=0))
    reshard_bytes = int(sub.last_transition.bytes_moved)
    warmup(sub)
    for _ in range(max(2, steps // 2)):
        one_step(sub, "submesh_degraded")
    t_deg = med_ms("submesh_degraded")

    # --- measured vs analytic bubble ---------------------------------------
    n_params = int(sum(
        np.asarray(x).size for x in jax.tree.leaves(emu.canonical_params())
    ))
    # comm-free Hardware isolates the model's schedule term: the factor
    # degenerates to exactly (m + pp - 1) / m
    hw = pm.Hardware(scaleup_bw=1e18, scaleout_bw=1e18)
    wl = pm.Workload(n_params=float(n_params), n_layers=cfg.n_layers,
                     d_model=cfg.d_model, seq_len=SEQ,
                     minibatch_tokens=float(D * LB * SEQ), act_bytes=4)
    par = pm.Parallel(tp=N1, pp=PP, dp=D, microbatch_seqs=LB // MB)
    it = pm.staged_iteration_time(hw, wl, par, (N1,) * PP)
    # measured-vs-analytic lands as a labeled gauge pair and the drift gate
    # reads the RECORDER's series, not function-local floats — the same
    # series a --telemetry run of the launcher exposes for offline diffing
    rec.gauge("bench.bubble_factor",
              it["total"] / (it["total"] - it["pp_bubble"]),
              source="analytic")
    rec.gauge("bench.bubble_factor", float(np.median(pair_ratios)),
              source="measured")
    analytic_factor = rec.values("bench.bubble_factor", source="analytic")[-1]
    measured_factor = rec.values("bench.bubble_factor", source="measured")[-1]
    rel_err = abs(measured_factor - analytic_factor) / analytic_factor

    # --- overlapped bucketed sync: off vs on, same degraded plan (§2.10) ---
    ov_kw = dict(local_batch=LB, optimizer=sgd(0.05),
                 key=jax.random.PRNGKey(0), pp=PP, microbatches=MB)
    ov_off = NTPSession.create(cfg, jax.make_mesh((D, N1), ("data", "model")),
                               overlap=False, **ov_kw)
    ov_on = NTPSession.create(cfg, jax.make_mesh((D, N1), ("data", "model")),
                              overlap=True, **ov_kw)
    for s in (ov_off, ov_on):
        warmup(s)
        # a degraded stage makes the sync heaviest (reshard→psum→reshard per
        # bucket/leaf) — the paper-relevant path and the largest collapse
        s.apply(FailureEvent(step=3, stage=1, domain=0))
        warmup(s)  # recompile for the degraded plan + donated layout
    for _ in range(steps):
        one_step(ov_off, "overlap_off")
        one_step(ov_on, "overlap_on")
    t_off, t_on = med_ms("overlap_off"), med_ms("overlap_on")
    # two probes each: the first compiles grads_fn/sync_fn, the second is
    # the steady-state sync wall time (train.sync spans land in the ring)
    for s in (ov_off, ov_on):
        s.measure_sync(batch())
    p_off, p_on = ov_off.measure_sync(batch()), ov_on.measure_sync(batch())
    sync_off_ms, sync_on_ms = p_off["sync_s"] * 1e3, p_on["sync_s"] * 1e3
    # serialized fake devices leave no backward window to hide the sync in,
    # so the model's exposed comm degenerates to the full bucketed sync and
    # the step prediction to the launch-collapse identity
    predicted_exposed_ms = pm.exposed_comm(sync_on_ms, 0.0)
    predicted_on_ms = (t_off - sync_off_ms) + predicted_exposed_ms
    measured_exposed_ms = max(0.0, t_on - (t_off - sync_off_ms))
    ov_rel_err = abs(predicted_on_ms - t_on) / t_on
    rec.gauge("bench.overlap_step_ms", t_off, mode="off")
    rec.gauge("bench.overlap_step_ms", t_on, mode="on")
    overlap_row = {
        "step_wall_ms": {"off": round(t_off, 1), "on": round(t_on, 1)},
        "sync_ms": {"off": round(sync_off_ms, 1), "on": round(sync_on_ms, 1)},
        "collectives": {"off": int(p_off["collectives"]),
                        "on": int(p_on["collectives"])},
        "exposed_ms": {"measured": round(measured_exposed_ms, 1),
                       "predicted": round(predicted_exposed_ms, 1)},
        "predicted_on_ms": round(predicted_on_ms, 1),
        "rel_err": round(ov_rel_err, 4),
        "tolerance": OVERLAP_REL_TOL,
        "within_tolerance": bool(ov_rel_err <= OVERLAP_REL_TOL),
        "on_faster": bool(t_on < t_off),
    }

    # --- per-kernel interpret vs compiled ----------------------------------
    krng = np.random.default_rng(1)
    q = jnp.asarray(krng.normal(size=(1, 2, 128, 32)), jnp.float32)
    k = jnp.asarray(krng.normal(size=(1, 1, 128, 32)), jnp.float32)
    xr = jnp.asarray(krng.normal(size=(256, 64)), jnp.float32)
    wr = jnp.ones((64,), jnp.float32)
    xs = jnp.asarray(krng.normal(size=(2, 64, 8)), jnp.float32)
    dts = jnp.asarray(krng.uniform(0.01, 0.2, size=(2, 64)), jnp.float32)
    As = jnp.asarray(-krng.uniform(0.5, 2.0, size=(2,)), jnp.float32)
    Bs = jnp.asarray(krng.normal(size=(2, 64, 16)) * 0.3, jnp.float32)
    src = jnp.asarray(krng.normal(size=(9, 64)), jnp.float32)
    idx = jnp.asarray(krng.integers(0, 9, size=(4, 3)), jnp.int32)
    calls = {
        "flash_attention": lambda i: ops.flash_attention(
            q, k, k, interpret=i),
        "rmsnorm": lambda i: ops.rmsnorm(xr, wr, block_rows=64, interpret=i),
        "ssd_scan": lambda i: ops.ssd_scan(xs, dts, As, Bs, Bs, chunk=32,
                                           interpret=i),
        "reshard_pack": lambda i: ops.reshard_pack(src, idx, interpret=i),
    }

    def time_us(f, n=3 if smoke else 10, label="misc"):
        jax.block_until_ready(f())
        with rec.span("bench.kernel_loop", label=label):
            for _ in range(n):
                jax.block_until_ready(f())
        dur = rec.spans("bench.kernel_loop", label=label)[-1]["dur"]
        return round(dur / n * 1e6, 1)

    kernels = {}
    for name, call in calls.items():
        row = {"interpret_us": time_us(lambda: call(True),
                                       label=f"{name}:interpret"),
               "compiled_us": None, "ratio": None, "note": ""}
        try:
            row["compiled_us"] = time_us(lambda: call(False),
                                         label=f"{name}:compiled")
            row["ratio"] = round(row["interpret_us"] / row["compiled_us"], 2)
        except Exception as e:  # noqa: BLE001 — CPU cannot lower Pallas
            # explicit skip marker: a null compiled column without it is
            # schema drift (the guard rejects bare nulls)
            row["skipped"] = "no accelerator"
            row["note"] = (f"backend {jax.default_backend()!r} cannot "
                           f"compile Pallas ({type(e).__name__})")
        # the dispatch counter the active recorder collected from
        # kernels.mode — proof of which mode each public wrapper resolved
        row["dispatches"] = {
            mode: int(rec.total("kernels.dispatch", kernel=name, mode=mode))
            for mode in ("interpret", "compiled")
        }
        kernels[name] = row

    train = {
        "config": {"model": "d64-L4-kv4", "pp": PP, "data": D, "n1": N1,
                   "local_batch": LB, "seq_len": SEQ, "microbatches": MB,
                   "steps_timed": steps, "smoke": smoke,
                   "backend": jax.default_backend()},
        "step_wall_ms": {"emulation": round(t_emu, 1),
                         "submesh": round(t_sub, 1),
                         "submesh_degraded": round(t_deg, 1)},
        "bubble": {
            "measured_factor": round(measured_factor, 4),
            "analytic_factor": round(analytic_factor, 4),
            "analytic_fraction": round(it["pp_bubble"] / it["total"], 4),
            "measured_fraction": round(1.0 - t_emu / t_sub, 4),
            "rel_err": round(rel_err, 4),
            "tolerance": BUBBLE_REL_TOL,
            "within_tolerance": bool(rel_err <= BUBBLE_REL_TOL),
        },
        "handoff": dict(handoff, reshard_transition_bytes=reshard_bytes),
        "kernels": kernels,
        "overlap": overlap_row,
    }

    # --- serve: continuous-batching decode loop ----------------------------
    from repro.configs.base import ArchConfig
    from repro.serve import Request, Router, ServeSession

    scfg = ArchConfig(
        arch_id="hotpath-serve-kv4", family="dense", citation="bench",
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
        d_ff=128, vocab_size=128, layer_pattern=("attn",),
    )
    n_req, max_new = (3, 4) if smoke else (8, 12)
    sess = ServeSession.create(scfg, replicas=1, n1=N1, slots=4, max_len=64,
                               prefill_len=16, key=jax.random.PRNGKey(0))
    router = Router(sess)
    srng = np.random.default_rng(0)
    for i in range(n_req):
        router.submit(Request(
            rid=i, max_new=max_new,
            prompt=srng.integers(1, 128, size=8).astype(np.int32)))
    guard = 0
    while router.queue or any(e.n_active for e in sess.engines):
        with rec.span("bench.serve_tick"):
            router.step()
        guard += 1
        assert guard < 2000, "serve bench did not converge"
    tick_ms = [s["dur"] * 1e3 for s in rec.spans("bench.serve_tick")]
    # first tick admits + prefills + compiles; steady-state is the tail
    steady = tick_ms[len(tick_ms) // 2:]
    decode_ms = float(np.median(steady))
    toks = n_req * max_new

    # KV reshard hot path: kernel route vs jnp route (interpret on CPU)
    from repro.reshard import engine as rse
    from repro.reshard import planner

    tables = planner.tables(planner.sync_key(8, N1, N1),
                            planner.sync_key(8, N1, 2), 8)
    kv = jnp.asarray(srng.normal(size=(N1, 8, 4, 16)), jnp.float32)
    jnp_us = time_us(lambda: rse.reshard_ranks(kv, tables, use_kernel=False),
                     label="kv_reshard:jnp")
    ker_us = time_us(lambda: rse.reshard_ranks(kv, tables, use_kernel=True),
                     label="kv_reshard:kernel")

    serve = {
        "config": {"arch": scfg.arch_id, "n1": N1, "slots": 4,
                   "requests": n_req, "max_new": max_new, "smoke": smoke,
                   "backend": jax.default_backend()},
        "prefill_and_decode": {
            "first_tick_ms": round(tick_ms[0], 1),       # admit+prefill+jit
            "decode_tick_ms": round(decode_ms, 2),
            "ticks": len(tick_ms),
            "tokens_decoded": toks,
            "tokens_per_s": round(toks / (sum(tick_ms) / 1e3), 1),
        },
        "kv_reshard": {
            "jnp_us": jnp_us, "kernel_us": ker_us,
            "kernel_over_jnp": round(ker_us / jnp_us, 2),
            "mode": ("interpret" if jax.default_backend() == "cpu"
                     else "compiled"),
        },
    }
    return {"train": train, "serve": serve}


def measure(smoke: bool = False) -> dict:
    """Spawn the measurement subprocess (needs its own XLA device count —
    jax may already be initialized in this process) and parse its report."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=16",
               # a fake-device rehearsal: never the chip, which this
               # process may already hold
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(REPO, "src"), REPO,
                    os.environ.get("PYTHONPATH", "")]))
    cmd = [sys.executable, "-m", "benchmarks.bench_hotpath", "--worker"]
    if smoke:
        cmd.append("--smoke")
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         cwd=REPO, timeout=2700)
    for line in reversed(out.stdout.splitlines()):
        if line.startswith("HOTPATH_JSON "):
            return json.loads(line[len("HOTPATH_JSON "):])
    raise RuntimeError(
        f"hotpath worker produced no report (rc={out.returncode}):\n"
        f"{out.stdout[-2000:]}\n{out.stderr[-2000:]}")


def _check_schema(path: str, want_keys: set, bench: str) -> list:
    """CI drift guard: the committed BENCH file's latest run must carry
    exactly the top-level keys this code produces."""
    errs = []
    if not os.path.exists(path):
        return [f"{os.path.basename(path)} missing"]
    with open(path) as f:
        doc = json.load(f)
    if doc.get("bench") != bench or not doc.get("runs"):
        errs.append(f"{os.path.basename(path)}: bad header/empty runs")
        return errs
    last = doc["runs"][-1]
    got = set(last) - {"date"}
    if got != want_keys:
        errs.append(f"{os.path.basename(path)}: run keys {sorted(got)} != "
                    f"expected {sorted(want_keys)}")
    if bench == "hotpath_train" and not errs:
        # kernel rows: a null compiled column must carry the explicit skip
        # marker, never a bare null
        for name, row in last.get("kernels", {}).items():
            if (row.get("compiled_us") is None
                    and row.get("skipped") != "no accelerator"):
                errs.append(f"kernel row {name!r}: null compiled_us without "
                            "an explicit 'skipped: no accelerator' note")
        want_ov = {"step_wall_ms", "sync_ms", "collectives", "exposed_ms",
                   "predicted_on_ms", "rel_err", "tolerance",
                   "within_tolerance", "on_faster"}
        missing = want_ov - set(last.get("overlap", {}))
        if missing:
            errs.append(f"overlap row missing keys {sorted(missing)}")
    return errs


def run():
    """benchmarks/run.py entry point — CSV rows from one full measurement."""
    m = measure(smoke=False)
    t, s = m["train"], m["serve"]
    w, b = t["step_wall_ms"], t["bubble"]
    return [
        {"name": "hotpath/train_step_ms/submesh", "value": w["submesh"],
         "derived": f"emulation={w['emulation']} "
                    f"degraded={w['submesh_degraded']}"},
        {"name": "hotpath/bubble_factor/measured",
         "value": b["measured_factor"],
         "derived": f"analytic={b['analytic_factor']} rel_err={b['rel_err']} "
                    f"tol={b['tolerance']} ok={b['within_tolerance']}"},
        {"name": "hotpath/handoff_bytes/step",
         "value": t["handoff"]["total_bytes"],
         "derived": f"reshard_transition="
                    f"{t['handoff']['reshard_transition_bytes']}"},
        {"name": "hotpath/overlap_step_ms/on",
         "value": t["overlap"]["step_wall_ms"]["on"],
         "derived": f"off={t['overlap']['step_wall_ms']['off']} "
                    f"collectives={t['overlap']['collectives']} "
                    f"rel_err={t['overlap']['rel_err']}"},
        {"name": "hotpath/serve_decode_tick_ms",
         "value": s["prefill_and_decode"]["decode_tick_ms"],
         "derived": f"tokens_per_s="
                    f"{s['prefill_and_decode']['tokens_per_s']}"},
    ]


def _append(path: str, bench: str, rec: dict) -> None:
    doc = {"bench": bench, "schema": 1, "runs": []}
    if os.path.exists(path):
        with open(path) as f:
            doc = json.load(f)
    rec["date"] = time.strftime("%Y-%m-%d")
    doc["runs"].append(rec)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(f"appended run {len(doc['runs'])} to {path}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small geometry + committed-BENCH schema check "
                         "(the CI bench-smoke contract); does not write")
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.worker:
        doc = _worker(args.smoke)
        print("HOTPATH_JSON " + json.dumps(doc))
        return

    m = measure(smoke=args.smoke)
    print(json.dumps(m, indent=2))
    if not m["train"]["bubble"]["within_tolerance"]:
        sys.exit("measured bubble factor outside the documented tolerance "
                 f"({m['train']['bubble']})")
    ov = m["train"]["overlap"]
    if not ov["within_tolerance"]:
        sys.exit("overlap-on step time disagrees with the launch-collapse "
                 f"prediction beyond the documented tolerance ({ov})")
    if not args.smoke and not ov["on_faster"]:
        sys.exit("overlap-on was not faster than overlap-off in a full run "
                 f"({ov})")
    if args.smoke:
        errs = (_check_schema(TRAIN_PATH, TRAIN_KEYS, "hotpath_train")
                + _check_schema(SERVE_PATH, SERVE_KEYS, "hotpath_serve"))
        if errs:
            sys.exit("BENCH schema drift:\n  " + "\n  ".join(errs))
        print("smoke ok: measurements in tolerance, BENCH schemas stable")
        return
    _append(TRAIN_PATH, "hotpath_train", m["train"])
    _append(SERVE_PATH, "hotpath_serve", m["serve"])


if __name__ == "__main__":
    main()
