"""BENCH_telemetry: the observability spine's own cost (ISSUE 8 satellite —
extends the BENCH_*.json series).

Two sections:

* **primitives** — ns per recorder operation (span enter/exit, counter,
  gauge) against a MemorySink, plus the NULL-recorder (telemetry off) cost
  of the same call sites — the number every instrumented hot path pays;
* **overhead** — a real `NTPSession.step` loop on fake devices, recorder
  off vs on. The GATE is the additive estimate (per-step event cost from
  the primitive timings ÷ measured step time): it must stay under
  ``OVERHEAD_PCT_MAX`` of the smoke step. The measured on-vs-off medians
  are recorded next to it as evidence, but the estimate is what's gated —
  differencing two ~100 ms step medians on a shared CPU host cannot
  resolve a sub-1% effect, the additive estimate can.

Usage:
  python -m benchmarks.bench_telemetry            # measure + append
  python -m benchmarks.bench_telemetry --smoke    # quick run + schema check
  (also a `run()` module for benchmarks/run.py CSV rows)
"""
import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
PATH = os.path.join(REPO, "BENCH_telemetry.json")

# recorder-on step overhead budget: the per-step telemetry work (1 span +
# 2 gauges in the orchestrated loop) must cost < 1% of a smoke step
OVERHEAD_PCT_MAX = 1.0

# schema keys the CI telemetry job pins (drift = hard failure)
TELEMETRY_KEYS = {"config", "primitives", "overhead"}


def _worker(smoke: bool) -> dict:
    import numpy as np

    import jax
    import jax.numpy as jnp

    from repro import telemetry
    from repro.optim import sgd
    from repro.runtime import NTPModelConfig, NTPSession
    from repro.telemetry import MemorySink, NULL, Recorder

    # --- primitives: ns per recorder op ------------------------------------
    n = 20_000 if smoke else 100_000

    def ns_per(f, reps=n):
        t0 = time.perf_counter()
        for _ in range(reps):
            f()
        return round((time.perf_counter() - t0) / reps * 1e9, 1)

    rec = Recorder(sinks=[MemorySink(maxlen=4096)])

    def one_span():
        with rec.span("bench.prim", k="v"):
            pass

    def null_span():
        with NULL.span("bench.prim", k="v"):
            pass

    primitives = {
        "span_ns": ns_per(one_span),
        "counter_ns": ns_per(lambda: rec.counter("bench.c", k="v")),
        "gauge_ns": ns_per(lambda: rec.gauge("bench.g", 1.0, k="v")),
        "hist_ns": ns_per(lambda: rec.hist("bench.h", 1.0, k="v")),
        "null_span_ns": ns_per(null_span),
        "null_gauge_ns": ns_per(lambda: NULL.gauge("bench.g", 1.0, k="v")),
        "ops_timed": n,
    }

    # --- overhead: a real session step, recorder off vs on -----------------
    D, N1 = 2, 4
    LB, SEQ = (4, 16) if smoke else (8, 32)
    steps = 6 if smoke else 10
    cfg = NTPModelConfig(d_model=64, n_kv_groups=4, q_per_kv=2, head_dim=16,
                         d_ff=256, unit_rows=64, n_layers=2, vocab=128)
    sess = NTPSession.create(
        cfg, jax.make_mesh((D, N1), ("data", "model")), local_batch=LB,
        optimizer=sgd(0.05), key=jax.random.PRNGKey(0),
    )
    rng = np.random.default_rng(0)

    def batch():
        return jnp.asarray(rng.integers(0, cfg.vocab, (D * LB, SEQ + 1)))

    def step_ms(recorder, n_steps):
        with telemetry.recording(recorder):
            for _ in range(2):
                m = sess.step(batch())
                jax.block_until_ready((sess.params, m["loss"]))
            ts = []
            for _ in range(n_steps):
                b = batch()
                t0 = time.perf_counter()
                m = sess.step(b)
                jax.block_until_ready((sess.params, m["loss"]))
                ts.append((time.perf_counter() - t0) * 1e3)
        return float(np.median(ts))

    off_ms = step_ms(None, steps)
    on_rec = Recorder(sinks=[MemorySink()])
    on_ms = step_ms(on_rec, steps)

    # the gated number: what the orchestrated loop's per-step telemetry
    # (1 session.step span + 2 goodput gauges) costs, from the primitive
    # timings, as a fraction of the MEASURED step
    per_step_ns = primitives["span_ns"] + 2 * primitives["gauge_ns"]
    overhead_pct = per_step_ns / (off_ms * 1e6) * 100.0

    return {
        "config": {"model": "d64-L2-kv4", "data": D, "n1": N1,
                   "local_batch": LB, "seq_len": SEQ, "steps_timed": steps,
                   "smoke": smoke, "backend": jax.default_backend()},
        "primitives": primitives,
        "overhead": {
            "step_ms_off": round(off_ms, 2),
            "step_ms_on": round(on_ms, 2),
            "per_step_telemetry_ns": round(per_step_ns, 1),
            "overhead_pct_estimate": round(overhead_pct, 5),
            "budget_pct": OVERHEAD_PCT_MAX,
            "within_budget": bool(overhead_pct < OVERHEAD_PCT_MAX),
            "events_recorded": len(on_rec.sinks[0]),
        },
    }


def measure(smoke: bool = False) -> dict:
    """Spawn the measurement subprocess (needs its own XLA device count)."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               # a fake-device rehearsal: never the chip, which this
               # process may already hold
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(REPO, "src"), REPO,
                    os.environ.get("PYTHONPATH", "")]))
    cmd = [sys.executable, "-m", "benchmarks.bench_telemetry", "--worker"]
    if smoke:
        cmd.append("--smoke")
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         cwd=REPO, timeout=1200)
    for line in reversed(out.stdout.splitlines()):
        if line.startswith("TELEMETRY_JSON "):
            return json.loads(line[len("TELEMETRY_JSON "):])
    raise RuntimeError(
        f"telemetry bench worker produced no report (rc={out.returncode}):\n"
        f"{out.stdout[-2000:]}\n{out.stderr[-2000:]}")


def _check_schema(path: str) -> list:
    """CI drift guard: the committed BENCH file's latest run must carry
    exactly the top-level keys this code produces."""
    errs = []
    if not os.path.exists(path):
        return [f"{os.path.basename(path)} missing"]
    with open(path) as f:
        doc = json.load(f)
    if doc.get("bench") != "telemetry" or not doc.get("runs"):
        errs.append(f"{os.path.basename(path)}: bad header/empty runs")
        return errs
    got = set(doc["runs"][-1]) - {"date"}
    if got != TELEMETRY_KEYS:
        errs.append(f"{os.path.basename(path)}: run keys {sorted(got)} != "
                    f"expected {sorted(TELEMETRY_KEYS)}")
    return errs


def run():
    """benchmarks/run.py entry point — CSV rows from one full measurement."""
    m = measure(smoke=False)
    p, o = m["primitives"], m["overhead"]
    return [
        {"name": "telemetry/span_ns", "value": p["span_ns"],
         "derived": f"counter={p['counter_ns']} gauge={p['gauge_ns']} "
                    f"null_span={p['null_span_ns']}"},
        {"name": "telemetry/step_overhead_pct",
         "value": o["overhead_pct_estimate"],
         "derived": f"budget={o['budget_pct']} ok={o['within_budget']} "
                    f"off_ms={o['step_ms_off']} on_ms={o['step_ms_on']}"},
    ]


def _append(rec: dict) -> None:
    doc = {"bench": "telemetry", "schema": 1, "runs": []}
    if os.path.exists(PATH):
        with open(PATH) as f:
            doc = json.load(f)
    rec["date"] = time.strftime("%Y-%m-%d")
    doc["runs"].append(rec)
    with open(PATH, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(f"appended run {len(doc['runs'])} to {PATH}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small geometry + committed-BENCH schema check "
                         "(the CI telemetry job's contract); does not write")
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.worker:
        doc = _worker(args.smoke)
        print("TELEMETRY_JSON " + json.dumps(doc))
        return

    m = measure(smoke=args.smoke)
    print(json.dumps(m, indent=2))
    if not m["overhead"]["within_budget"]:
        sys.exit("recorder-on step overhead above budget "
                 f"({m['overhead']})")
    if args.smoke:
        errs = _check_schema(PATH)
        if errs:
            sys.exit("BENCH schema drift:\n  " + "\n  ".join(errs))
        print("smoke ok: overhead within budget, BENCH schema stable")
        return
    _append(m)


if __name__ == "__main__":
    main()
