import os
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=512")))
# ^ first lines, before any jax import (see dryrun.py).
"""Profiler with two modes.

Dry-run (default): compile one (arch × shape) at the production mesh and
print the top byte/FLOP contributors from the optimized HLO — the 'profile'
that drives §Perf hypotheses (no real-TPU timings exist here).

  PYTHONPATH=src python -m repro.launch.profile --arch chameleon-34b --shape train_4k

Measured (``--measure``, ISSUE 7): run the SAME pp=2 plan through the
stage-sequential emulation AND the measured submesh pipeline
(core/pp_submesh) on fake devices, print per-step `time.perf_counter` wall
times plus the measured-vs-analytic bubble factor and the cross-stage
hand-off byte table; ``--trace-dir`` additionally wraps the timed steps in
`jax.profiler.trace` so the per-op timeline can be inspected offline.

  PYTHONPATH=src python -m repro.launch.profile --measure --steps 5 \
      --trace-dir /tmp/ntp-trace
"""
import argparse
import time


def _dryrun(args):
    from repro.configs import get_arch, get_shape
    from repro.launch.hlo_analysis import analyze_hlo, top_contributors
    from repro.launch.mesh import dp_axes, make_production_mesh
    from repro.train.steps import make_setup

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    su = make_setup(get_arch(args.arch), get_shape(args.shape), mesh,
                    dp_axes=dp_axes(mesh))
    with mesh:
        compiled = su.jit_step().lower(*su.abstract_args()).compile()
    txt = compiled.as_text()
    tot = analyze_hlo(txt)
    print(f"total: {tot['flops']/1e12:.2f} TF, {tot['bytes']/1e12:.2f} TB, "
          f"coll {tot['collective_moved_bytes']/1e12:.2f} TB moved")
    print(f"{'op:jax_op_name':60s} {'GB':>10s} {'TF':>8s} {'count':>7s}")
    for row in top_contributors(txt, args.top):
        print(f"{row['key'][:60]:60s} {row['bytes']/1e9:10.1f} "
              f"{row['flops']/1e12:8.2f} {row['count']:7.0f}")


def _measure(args):
    import contextlib

    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_staged_mesh
    from repro.optim import sgd
    from repro.runtime import NTPModelConfig, NTPSession

    pp, d, n1 = args.pp, 2, 4
    lb, seq, mb = args.batch, args.seq_len, args.microbatches
    cfg = NTPModelConfig(d_model=64, n_kv_groups=4, q_per_kv=2, head_dim=16,
                         d_ff=256, unit_rows=64, n_layers=2 * pp, vocab=128)
    kw = dict(local_batch=lb, optimizer=sgd(0.05), key=jax.random.PRNGKey(0),
              pp=pp, microbatches=mb, overlap=args.overlap)
    emu = NTPSession.create(cfg, jax.make_mesh((d, n1), ("data", "model")),
                            **kw)
    sub = NTPSession.create(cfg, make_staged_mesh(pp, d, n1), **kw)
    rng = np.random.default_rng(0)

    def batch():
        return jnp.asarray(rng.integers(0, cfg.vocab, (d * lb, seq + 1)))

    def timed(sess, name):
        for _ in range(2):   # compile + donated-layout recompile warmup
            m = sess.step(batch())
            jax.block_until_ready((sess.params, m["loss"]))
        ts = []
        for i in range(args.steps):
            b = batch()
            t0 = time.perf_counter()
            m = sess.step(b)
            jax.block_until_ready((sess.params, m["loss"]))
            ts.append((time.perf_counter() - t0) * 1e3)
            print(f"  {name} step {i}: {ts[-1]:8.1f} ms  "
                  f"loss {float(m['loss']):.4f}")
        return float(np.median(ts)), m

    trace = (jax.profiler.trace(args.trace_dir) if args.trace_dir
             else contextlib.nullcontext())
    with trace:
        print(f"emulation: pp={pp} on a ({d}, {n1}) mesh, stage-sequential")
        t_emu, _ = timed(emu, "emu")
        print(f"submesh:   pp={pp} on a ({pp}, {d}, {n1}) staged mesh, "
              "ppermute hand-off")
        t_sub, ms = timed(sub, "sub")

    analytic = (mb + pp - 1) / mb
    for sess, name in ((emu, "emu"), (sub, "sub")):
        s = sess.measure_sync(batch())
        print(f"  {name} sync probe (overlap {s['overlap']}): "
              f"{s['sync_s'] * 1e3:.1f} ms, {s['collectives']} collectives")
    print(f"\nper-step median: emulation {t_emu:.1f} ms, "
          f"submesh {t_sub:.1f} ms")
    print(f"bubble factor: measured {t_sub / t_emu:.3f} vs analytic "
          f"(m+pp-1)/m = {analytic:.3f} "
          f"(rel err {abs(t_sub / t_emu - analytic) / analytic:.3f}; "
          "bench_hotpath gates this at its documented tolerance)")
    print(f"pipeline ticks/step: {ms['pipeline_ticks']}")
    print("cross-stage hand-off (bytes, from the ppermute transfer shapes):")
    for k, v in ms["handoff"].items():
        print(f"  {k:20s} {v}")
    if args.trace_dir:
        print(f"profiler trace written to {args.trace_dir}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--measure", action="store_true",
                    help="time the emulated vs submesh pp step instead of "
                         "dry-run HLO analysis")
    ap.add_argument("--trace-dir", default=None,
                    help="with --measure: jax.profiler trace output dir")
    ap.add_argument("--telemetry", default=None, metavar="OUT.jsonl",
                    help="with --measure: record session.step spans etc. as "
                         "JSONL (repro.telemetry)")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--pp", type=int, default=2)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8,
                    help="with --measure: per-replica batch")
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--overlap", choices=["on", "off"], default="off",
                    help="with --measure: overlapped bucketed gradient sync "
                         "(core/overlap, DESIGN.md §2.10) in both sessions")
    args = ap.parse_args()
    if args.telemetry and not args.measure:
        ap.error("--telemetry needs --measure (dry-run has no timed steps)")
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.measure:
        if args.telemetry:
            from repro import telemetry

            telemetry.configure(jsonl=args.telemetry)
        _measure(args)
        if args.telemetry:
            telemetry.shutdown()
            print(f"telemetry stream written to {args.telemetry}")
        return
    if not (args.arch and args.shape):
        ap.error("--arch and --shape are required unless --measure is set")
    _dryrun(args)


if __name__ == "__main__":
    main()
