"""Training launcher.

Examples (CPU container — reduced configs execute, full configs dry-run):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --reduced \\
      --steps 20 --seq-len 128 --batch 8
  PYTHONPATH=src python -m repro.launch.train --arch gemma2-9b --dry-run

NTP mode — train the nonuniform-TP prototype through the runtime session and
inject a mid-run GPU failure (consumed as a FailureEvent, replanned in
place):
  PYTHONPATH=src python -m repro.launch.train --ntp --devices 8 \\
      --steps 40 --fail-at 20 [--fail-replica 1]

Trace mode — replay a Llama3-calibrated failure/recovery trace through the
lifecycle orchestrator (fail -> boost -> repair; DESIGN.md §2.4), with the
power policy deciding NTP vs NTP-PW per transition:
  PYTHONPATH=src python -m repro.launch.train --ntp --devices 8 --steps 200 \\
      --trace 2e5 --trace-seed 0 --power-policy ntp_pw
"""
import argparse
import os
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="arch config id (required unless --ntp)")
    ap.add_argument("--ntp", action="store_true",
                    help="train the NTP prototype via the runtime session")
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline stages for the NTP prototype (stage-"
                         "partitioned layers, per-(replica, stage) health; "
                         "a failure degrades only its stage — DESIGN.md §2.6)")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="1F1B microbatch chunks per step (NTP mode; must "
                         "divide --batch)")
    ap.add_argument("--overlap", choices=["on", "off"], default="off",
                    help="overlapped, bucketed gradient sync (core/overlap, "
                         "DESIGN.md §2.10): hide the DP all-reduce / NTP "
                         "reshard chain behind backward compute; 'off' is "
                         "bit-identical to the pre-overlap step")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a GPU failure before this step (NTP mode)")
    ap.add_argument("--fail-replica", type=int, default=1,
                    help="DP replica whose scale-up domain loses a GPU")
    ap.add_argument("--fail-stage", type=int, default=None,
                    help="pipeline stage the failure lands on (--pp > 1; "
                         "default: the replica's worst stage)")
    ap.add_argument("--fail-gpus", type=int, default=1,
                    help="GPUs lost in the failure event")
    ap.add_argument("--trace", type=float, default=None, metavar="RATE_MULT",
                    help="replay a Llama3-calibrated fail/repair trace at "
                         "this failure-rate multiplier (NTP mode; try 1e5+ — "
                         "the tiny test cluster needs a huge multiplier to "
                         "see events in a short run)")
    ap.add_argument("--trace-seed", type=int, default=0,
                    help="trace sampler seed (default 0)")
    ap.add_argument("--trace-mix", default=None, metavar="KIND=RATE[,...]",
                    help="mix degradation kinds into the sampled trace: "
                         "comma list of straggler=R, link=R, sdc=R onset "
                         "rates as multiples of the binary failure rate "
                         "(e.g. straggler=0.5,sdc=0.1); needs --trace")
    ap.add_argument("--quarantine", choices=["on", "off"], default="on",
                    help="SDC policy (default on): quarantine the suspect "
                         "replica and roll back to the canonical snapshot; "
                         "off = ledger the suspicion and keep training")
    ap.add_argument("--steps-per-hour", type=float, default=1.0,
                    help="training steps per simulated trace hour")
    ap.add_argument("--power-policy", choices=["ntp", "ntp_pw"], default=None,
                    help="per-transition NTP vs NTP-PW decision hook "
                         "(default: ntp when --trace is given)")
    ap.add_argument("--allocator", choices=["greedy", "off"], default="off",
                    help="global repack planner for --pp > 1 (repro.cluster, "
                         "DESIGN.md §2.7): spares assignable to ANY stage, "
                         "cost-priced cross-stage swaps; 'off' keeps PR-5 "
                         "stage-local packing")
    ap.add_argument("--spares", type=int, default=0,
                    help="spare scale-up domains absorbing the worst "
                         "failures (NTP mode; --pp > 1 needs --allocator "
                         "greedy)")
    ap.add_argument("--allocator-horizon", type=int, default=200,
                    help="amortization horizon (steps) a priced move must "
                         "pay for itself within (--allocator greedy)")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--reduced", action="store_true",
                    help="train the smoke-scale variant of the arch family")
    ap.add_argument("--dry-run", action="store_true",
                    help="lower+compile at the production mesh instead of running")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--devices", type=int, default=0,
                    help="devices of the (2, n/2) NTP mesh (default: every "
                         "visible device); on CPU this many fake host "
                         "devices are added to XLA_FLAGS")
    ap.add_argument("--telemetry", default=None, metavar="OUT.jsonl",
                    help="record the run's telemetry stream (spans, "
                         "counters, gauges) as JSONL; fold it offline with "
                         "python -m repro.launch.telemetry_report OUT.jsonl")
    args = ap.parse_args()
    if args.telemetry:
        from repro import telemetry

        telemetry.configure(jsonl=args.telemetry)
    if args.arch is None and not args.ntp:
        ap.error("--arch is required unless --ntp is given")
    if args.ntp and args.dry_run:
        ap.error("--ntp has no --dry-run path; use python -m "
                 "repro.launch.dryrun_ntp for compile-only NTP accounting")
    if (args.trace is not None or args.power_policy) and not args.ntp:
        ap.error("--trace/--power-policy need --ntp (lifecycle orchestration "
                 "is NTP-backend-only)")
    if args.trace is not None and args.fail_at is not None:
        ap.error("--trace and --fail-at are mutually exclusive")
    args.trace_mix_kwargs = {}
    if args.trace_mix is not None:
        if args.trace is None:
            ap.error("--trace-mix needs --trace (the mix rates scale the "
                     "same sampled trace)")
        from repro.core.failure_model import parse_trace_mix

        try:
            args.trace_mix_kwargs = parse_trace_mix(args.trace_mix)
        except ValueError as e:
            ap.error(f"--trace-mix: {e}")
    if args.quarantine == "off" and not args.ntp:
        ap.error("--quarantine is the NTP SDC rollback policy; it needs "
                 "--ntp")
    if args.pp != 1 or args.microbatches != 1:
        if not args.ntp:
            ap.error("--pp/--microbatches need --ntp (stage-partitioned "
                     "training is NTP-backend-only)")
        from repro.configs.shapes import SUPPORTED_PP

        if args.pp not in SUPPORTED_PP:
            ap.error(f"--pp {args.pp} not in supported ladder {SUPPORTED_PP}")
    if args.fail_stage is not None and args.pp == 1:
        ap.error("--fail-stage needs --pp > 1")
    if args.overlap == "on" and not args.ntp:
        ap.error("--overlap needs --ntp (the overlapped bucketed sync is "
                 "NTP-backend-only)")
    if (args.allocator != "off" or args.spares) and not args.ntp:
        ap.error("--allocator/--spares need --ntp (lifecycle replanning is "
                 "NTP-backend-only)")
    if args.allocator != "off" and args.pp == 1:
        ap.error("--allocator is the pp>1 global repack planner; pp=1 "
                 "sessions already pack globally (--spares works directly)")
    if args.spares and args.pp > 1 and args.allocator == "off":
        ap.error("spares with --pp > 1 need the global allocator: pass "
                 "--allocator greedy")

    host_devices = 512 if args.dry_run else args.devices
    if host_devices:
        os.environ["XLA_FLAGS"] = " ".join(filter(None, (
            os.environ.get("XLA_FLAGS"),
            f"--xla_force_host_platform_device_count={host_devices}",
        )))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.ntp:
        _run_ntp(args)
        return

    import jax
    import jax.numpy as jnp

    from repro.configs import get_arch, get_shape, reduced
    from repro.configs.shapes import ShapeSpec
    from repro.checkpoint import save_checkpoint
    from repro.data.pipeline import DataConfig, SyntheticLMPipeline
    from repro.launch.mesh import dp_axes, make_production_mesh, make_test_mesh
    from repro.optim import AdamWConfig, adamw_init
    from repro.train.steps import make_setup

    cfg = get_arch(args.arch)

    if args.dry_run:
        from repro.launch.dryrun import run_one

        rec = run_one(args.arch, args.shape, args.multi_pod)
        print(rec)
        return

    cfg = reduced(cfg) if args.reduced else cfg
    shape = ShapeSpec("cli", args.seq_len, args.batch, "train")
    mesh = None
    if args.devices:
        mesh = make_test_mesh(2, args.devices // 2)
    opt_cfg = AdamWConfig(lr=args.lr)
    su = make_setup(cfg, shape, mesh, param_dtype=jnp.float32, opt_cfg=opt_cfg,
                    dp_axes=("data",) if mesh else ("data",))
    step = su.jit_step()

    key = jax.random.PRNGKey(args.seed)
    if mesh is not None:
        params = jax.jit(su.model.init, out_shardings=su.param_sharding)(key)
        opt = jax.jit(lambda p: adamw_init(p, opt_cfg),
                      out_shardings=su.opt_sharding)(params)
    else:
        params = su.model.init(key)
        opt = adamw_init(params, opt_cfg)
    n_par = sum(p.size for p in jax.tree.leaves(params))
    print(f"arch={cfg.arch_id} params={n_par/1e6:.1f}M devices={len(jax.devices())}")

    pipe = SyntheticLMPipeline(
        DataConfig(cfg.vocab_size, args.seq_len, args.batch, seed=args.seed), mesh
    )
    t0 = time.time()
    for i in range(args.steps):
        batch = pipe.batch(i)
        if su.cfg.encoder is not None:
            batch["enc_input"] = jnp.zeros(
                (args.batch, cfg.encoder.enc_seq, cfg.d_model), jnp.float32
            )
        params, opt, metrics = step(params, opt, batch)
        if i % args.log_every == 0 or i == args.steps - 1:
            print(
                f"step {i:5d}  loss {float(metrics['loss']):.4f}  "
                f"gnorm {float(metrics['grad_norm']):.3f}  "
                f"({(time.time()-t0):.1f}s)", flush=True,
            )
        if args.ckpt and args.ckpt_every and (i + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt, {"params": params, "opt": opt}, step=i + 1)
            print(f"  saved checkpoint -> {args.ckpt}")
    if args.ckpt:
        save_checkpoint(args.ckpt, {"params": params, "opt": opt}, step=args.steps)
        print(f"final checkpoint -> {args.ckpt}")


def _run_ntp(args) -> None:
    """NTP prototype through the runtime session, with an optional injected
    mid-training failure (--fail-at) or a full trace-driven fail/repair
    lifecycle (--trace) — the paper's scenario as launcher flags."""
    import jax
    import jax.numpy as jnp

    from repro.data.pipeline import DataConfig, SyntheticLMPipeline
    from repro.launch.mesh import make_test_mesh
    from repro.optim import AdamWConfig, adamw
    from repro.runtime import FailureEvent, NTPModelConfig, NTPSession, power_policy

    devices = jax.devices()
    n_dev = args.devices or len(devices)
    if n_dev < 2 or n_dev % 2 or len(devices) < n_dev:
        raise SystemExit(
            f"--ntp trains 2 DP replicas on a (2, n/2) mesh: it needs an "
            f"even number >= 2 of devices, asked for {n_dev} and "
            f"{len(devices)} {devices[0].platform} device(s) are visible "
            f"(on CPU, --devices N adds N fake host devices)"
        )
    if args.fail_at is not None and not 0 <= args.fail_replica < 2:
        raise SystemExit(
            f"--fail-replica {args.fail_replica} out of range for 2 DP replicas"
        )
    mesh = make_test_mesh(2, n_dev // 2)
    n1 = n_dev // 2
    cfg = NTPModelConfig(
        d_model=256, n_kv_groups=2 * n1, q_per_kv=2, head_dim=32,
        d_ff=max(512, 128 * n1), unit_rows=128,
        n_layers=max(2, 2 * args.pp), vocab=2048,
    )
    policy_name = args.power_policy or ("ntp" if args.trace is not None else None)
    from repro.cluster import make_allocator

    allocator = make_allocator(args.allocator,
                               horizon_steps=args.allocator_horizon)
    session = NTPSession.create(
        cfg, mesh, local_batch=args.batch,
        optimizer=adamw(AdamWConfig(lr=args.lr)),
        key=jax.random.PRNGKey(args.seed),
        power_policy=power_policy(policy_name) if policy_name else None,
        pp=args.pp, microbatches=args.microbatches,
        spares=args.spares, allocator=allocator,
        overlap=args.overlap, quarantine=args.quarantine == "on",
    )
    n_par = sum(p.size for p in jax.tree.leaves(session.canonical_params()))
    print(f"ntp prototype: {n_par/1e6:.1f}M params  mesh data=2 model={n1}  "
          + (f"pp={args.pp} stages {session.stage_boundaries}  "
             if args.pp > 1 else "")
          + f"plan {session.plan}"
          + (f"  overlap {args.overlap}" if args.overlap == "on" else "")
          + (f"  policy {policy_name}" if policy_name else "")
          + (f"  allocator {args.allocator} spares {args.spares}"
             if args.allocator != "off" or args.spares else ""))

    pipe = SyntheticLMPipeline(
        DataConfig(cfg.vocab, args.seq_len, 2 * args.batch, seed=args.seed)
    )

    if args.trace is not None:
        _run_ntp_trace(args, session, pipe)
        return

    t0 = time.time()
    for i in range(args.steps):
        if args.fail_at is not None and i == args.fail_at:
            plan = session.apply(
                FailureEvent(step=i, replica=args.fail_replica,
                             n_gpus=args.fail_gpus, stage=args.fail_stage)
            )
            stage_s = (f"stage={args.fail_stage}, "
                       if args.fail_stage is not None else "")
            print(f"*** step {i}: FailureEvent(replica={args.fail_replica}, "
                  f"{stage_s}n_gpus={args.fail_gpus}) "
                  f"-> plan {plan} mode {session.mode.value}")
        metrics = session.step(jnp.asarray(pipe._batch_np(i)))
        if i % args.log_every == 0 or i == args.steps - 1:
            srel = metrics.get("stage_rel_iter_time")
            print(
                f"step {i:5d}  loss {float(metrics['loss']):.4f}  "
                f"gnorm {float(metrics['grad_norm']):.3f}  "
                + (f"stage_rel {tuple(round(r, 3) for r in srel)}  "
                   if srel is not None else "")
                + f"({(time.time()-t0):.1f}s)", flush=True,
            )
        if args.ckpt and args.ckpt_every and (i + 1) % args.ckpt_every == 0:
            session.save(args.ckpt)
            print(f"  saved canonical checkpoint -> {args.ckpt}")
    if args.ckpt:
        session.save(args.ckpt)
        print(f"final canonical checkpoint -> {args.ckpt}")


def _run_ntp_trace(args, session, pipe) -> None:
    """Replay a sampled failure/recovery trace against the live session via
    the lifecycle orchestrator (DESIGN.md §2.4)."""
    import jax.numpy as jnp

    from repro.core.failure_model import FailureTraceConfig
    from repro.runtime import TraceRunner, event_kind, schedule_from_trace

    d, n1 = session.plan.d, session.plan.n1
    pp = session.pp
    trace_cfg = FailureTraceConfig(
        n_gpus=d * pp * n1, domain_size=n1,
        days=args.steps / args.steps_per_hour / 24.0,
        rate_multiplier=args.trace, seed=args.trace_seed,
        **args.trace_mix_kwargs,
    )
    schedule = schedule_from_trace(
        trace_cfg, steps=args.steps, steps_per_hour=args.steps_per_hour,
        pp=pp,
    )
    from collections import Counter

    kinds = Counter(event_kind(s.event) for s in schedule)
    print(f"trace: {len(schedule)} events over {args.steps} steps "
          f"({', '.join(f'{k}={n}' for k, n in sorted(kinds.items()))})")

    t0 = time.time()

    def on_event(ev, plan):
        kind = event_kind(ev)
        site = (f"stage {ev.stage} domain {ev.domain}"
                if ev.stage is not None else f"domain {ev.domain}")
        print(f"*** step {ev.step}: {kind} {site} -> plan {plan}  "
              f"local_batches {session.local_batches}")
        gp = session.last_global_plan
        if gp is not None and (gp.spare_sites or gp.swaps):
            print(f"    allocator: spares at {gp.spare_sites} swaps "
                  f"{gp.swaps} predicted {gp.predicted_bytes}B "
                  f"(goodput {gp.goodput:.3f} vs stage-local "
                  f"{gp.baseline_goodput:.3f})")

    runner = TraceRunner(session, schedule, on_event=on_event)
    log_every = max(args.log_every, 1)
    for start in range(0, args.steps, log_every):
        n = min(log_every, args.steps - start)
        hist = runner.run(lambda i: jnp.asarray(pipe._batch_np(i)), n)
        h = hist[-1]
        extra = (f"  boost {h['power_boost']:.2f}  rel_iter "
                 f"{h['rel_iter_time']:.3f}" if "power_boost" in h else "")
        print(f"step {h['step']:5d}  loss {h['loss']:.4f}  "
              f"gnorm {h['grad_norm']:.3f}  tp {h['replica_tp']}{extra}  "
              f"({time.time() - t0:.1f}s)", flush=True)
    s = runner.summary()
    by_kind = ", ".join(f"{k}={v}" for k, v in sorted(
        s["events_by_kind"].items()))
    roll = f", rollbacks {s['rollbacks']}" if s.get("rollbacks") else ""
    print(f"lifecycle: {by_kind or 'no events'}{roll}, "
          f"goodput {s['goodput']:.3f}, final plan {s['final_plan']}")
    if args.ckpt:
        session.save(args.ckpt)
        print(f"final canonical checkpoint -> {args.ckpt}")


if __name__ == "__main__":
    main()

