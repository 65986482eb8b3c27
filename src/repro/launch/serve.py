"""Serving launcher: continuous-batching NTP inference under a
failure/recovery trace (DESIGN.md §2.5).

Examples (CPU container — smoke-scale archs execute):

  # 60 requests through one healthy replica
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --requests 60

  # the paper's scenario, serving-side: replay a Llama3-calibrated
  # fail/repair trace against 2 live replicas; the KV cache reshards
  # mid-decode instead of dropping in-flight requests
  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-9b \\
      --replicas 2 --requests 120 --trace 2e2 --policy ntp_pw

  # the baseline for comparison: any failure drops the whole replica
  PYTHONPATH=src python -m repro.launch.serve --replicas 2 --requests 120 \\
      --trace 2e2 --policy drop
"""
import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b",
                    help="arch config id (served at reduced/smoke scale "
                         "unless --full)")
    ap.add_argument("--full", action="store_true",
                    help="serve the full-size config (slow on CPU)")
    ap.add_argument("--policy", choices=["drop", "ntp", "ntp_pw"],
                    default="ntp_pw")
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--tp", type=int, default=4,
                    help="scale-up domain width (ranks per replica)")
    ap.add_argument("--slots", type=int, default=8,
                    help="KV-cache slots per replica (continuous batching)")
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--prefill-len", type=int, default=32)
    ap.add_argument("--requests", type=int, default=60)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--arrival-every", type=float, default=1.0,
                    help="mean ticks between request arrivals")
    ap.add_argument("--slo", type=float, default=0.0, metavar="TICKS",
                    help="per-request completion deadline: arrival + SLO "
                         "ticks (0 = no SLO; admission rejects hopeless "
                         "requests up front)")
    ap.add_argument("--trace", type=float, default=None, metavar="RATE_MULT",
                    help="replay a Llama3-calibrated fail/repair trace at "
                         "this failure-rate multiplier (~2e2 suits the tiny "
                         "default cluster: hardware repairs take 72-120 "
                         "ticks, so much hotter rates drown the replica)")
    ap.add_argument("--trace-seed", type=int, default=0)
    ap.add_argument("--trace-mix", default=None, metavar="KIND=RATE[,...]",
                    help="mix degradation kinds into the sampled trace: "
                         "comma list of straggler=R, link=R, sdc=R onset "
                         "rates as multiples of the binary failure rate "
                         "(e.g. straggler=0.5,sdc=0.1); needs --trace")
    ap.add_argument("--quarantine", choices=["on", "off"], default="on",
                    help="SDC policy (default on): drain the suspect "
                         "replica (in-flight requests finish, no new "
                         "admits) until the clear; off = reprice only")
    ap.add_argument("--ticks-per-hour", type=float, default=1.0,
                    help="serving wall ticks per simulated trace hour")
    ap.add_argument("--max-ticks", type=int, default=5000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=25)
    ap.add_argument("--use-kernel", action="store_true",
                    help="route reshard send-bucket packing through the "
                         "Pallas reshard_pack kernel")
    ap.add_argument("--telemetry", default=None, metavar="OUT.jsonl",
                    help="record the run's telemetry stream (admission, "
                         "preemptions, TTFT/TPOT, transition spans) as "
                         "JSONL; fold it offline with python -m "
                         "repro.launch.telemetry_report OUT.jsonl")
    args = ap.parse_args()
    trace_mix_kwargs = {}
    if args.trace_mix is not None:
        if args.trace is None:
            ap.error("--trace-mix needs --trace (the mix rates scale the "
                     "same sampled trace)")
        from repro.core.failure_model import parse_trace_mix

        try:
            trace_mix_kwargs = parse_trace_mix(args.trace_mix)
        except ValueError as e:
            ap.error(f"--trace-mix: {e}")
    if args.quarantine == "off" and args.trace is None:
        ap.error("--quarantine shapes the trace-driven SDC response; it "
                 "needs --trace")
    if args.telemetry:
        from repro import telemetry

        telemetry.configure(jsonl=args.telemetry)

    import numpy as np
    import jax

    from repro.configs import get_arch, reduced
    from repro.core.failure_model import FailureTraceConfig
    from repro.launch.compile_cache import enable_compile_cache
    from repro.runtime import event_kind, schedule_from_trace
    from repro.serve import Request, Router, ServeSession

    enable_compile_cache()
    cfg = get_arch(args.arch)
    if not args.full:
        cfg = reduced(cfg)

    session = ServeSession.create(
        cfg, replicas=args.replicas, n1=args.tp, slots=args.slots,
        max_len=args.max_len, prefill_len=args.prefill_len,
        policy=args.policy, key=jax.random.PRNGKey(args.seed),
        use_kernel=args.use_kernel, quarantine=args.quarantine == "on",
    )
    router = Router(session)
    n_par = sum(p.size for p in jax.tree.leaves(session.params))
    print(f"serve: arch={cfg.arch_id} params={n_par/1e6:.1f}M "
          f"replicas={args.replicas}×TP{args.tp} slots={args.slots} "
          f"policy={args.policy}")

    schedule = []
    if args.trace is not None:
        trace_cfg = FailureTraceConfig(
            n_gpus=args.replicas * args.tp, domain_size=args.tp,
            days=args.max_ticks / args.ticks_per_hour / 24.0,
            rate_multiplier=args.trace, seed=args.trace_seed,
            **trace_mix_kwargs,
        )
        schedule = schedule_from_trace(
            trace_cfg, steps=args.max_ticks, steps_per_hour=args.ticks_per_hour
        )
        from collections import Counter

        kinds = Counter(event_kind(s.event) for s in schedule)
        print(f"trace: {len(schedule)} events "
              f"({', '.join(f'{k}={n}' for k, n in sorted(kinds.items()))})")

    rng = np.random.default_rng(args.seed)
    arrivals = np.cumsum(
        rng.exponential(args.arrival_every, args.requests)
    ).astype(int)
    reqs = [
        Request(
            rid=i,
            prompt=rng.integers(1, cfg.vocab_size,
                                size=max(1, args.prompt_len)).astype(np.int32),
            max_new=args.max_new,
            deadline=(float(arrivals[i]) + args.slo) if args.slo else None,
        )
        for i in range(args.requests)
    ]

    t0 = time.time()
    next_req = 0
    tick = 0
    while tick < args.max_ticks:
        while schedule and schedule[0].step <= tick:
            ev = schedule.pop(0).event
            router.apply(ev)
            print(f"*** tick {tick}: {event_kind(ev)} domain {ev.domain} -> "
                  f"tp {session.replica_tp} "
                  f"speeds {[round(e.rel_speed, 3) for e in session.engines]}")
        while next_req < len(reqs) and arrivals[next_req] <= tick:
            router.submit(reqs[next_req])
            next_req += 1
        router.step()
        tick += 1
        if tick % args.log_every == 0:
            g = router.goodput()
            print(f"tick {tick:5d}  done {g['completed']:4d}/{args.requests}"
                  f"  queue {len(router.queue):3d}"
                  f"  tok/tick {g['tokens_per_tick']:.2f}"
                  f"  ({time.time()-t0:.1f}s)", flush=True)
        if (next_req == len(reqs) and not router.queue
                and all(e.n_active == 0 for e in session.engines)):
            break

    g = router.goodput()
    print(f"served {g['completed']}/{args.requests} requests in {tick} ticks "
          f"({time.time()-t0:.1f}s wall): goodput {g['tokens_per_tick']:.2f} "
          f"tok/tick, SLO attainment {g['slo_attainment']:.3f}, "
          f"{g['rejected']} rejected, {g['preemptions']} preemptions")
    for r, e in enumerate(session.engines):
        print(f"  replica {r}: tp {e.tp} tokens {e.stats['tokens']} "
              f"reshards {e.stats['reshards']} "
              f"({e.stats['reshard_bytes']/1e3:.1f} kB moved)")


if __name__ == "__main__":
    main()
