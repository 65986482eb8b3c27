"""Find a serving cell's knee once, when the cell is defined: run it at
each of a list of rates, in rising order, in one process, and print for
each rate and seed whether the queue held over the window. The benchmark's
own runs never do this; a cell then offers load at the fixed rate in its
traffic file.

    python3 bench/sweep.py --workload <name> --rates 0.5,0.75,1.0 \\
        --seeds 1,2,3 --seconds 30

A rate is sustained where, on every seed, the queue at the window's end is
no longer than at its start plus one and no request was refused; the knee
is the highest rate below which every rate was sustained, so the sweep
stops at the first rate that is not. Each run is the cell's own: its
arrivals, warm-up and window, with the reference skipped (no sample is
checked). One JSON line per run, then one with the knee.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def _reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    devices = harness.accelerator_devices(cell.chips)
    harness.configure_compile_cache()
    meter = harness.CompileMeter()
    base = dict(cell.traffic)
    held = {}
    for rate in sorted(float(x) for x in args.rates.split(",")):
        ok = True
        for seed in (int(x) for x in args.seeds.split(",")):
            cell.traffic = dict(base, rate=rate, check_requests=0)
            t0 = time.time()
            run = harness.Run(cell=cell, seed=seed, seconds=args.seconds,
                              trace=False, devices=devices, t_process=t0,
                              meter=meter, limits={})
            out = cell.driver.run(run)
            w = out.records["window"]
            kept = (w["queue_at_end"] <= w["queue_at_start"] + 1
                    and w["rejected"] == 0)
            ok = ok and kept
            ttft = _reader("ttft_p50_ms.serve").read(run, out.records, None)
            print(json.dumps({
                "rate": rate, "seed": seed, "held": kept,
                "queue": [w["queue_at_start"], w["queue_at_end"]],
                "rejected": w["rejected"], "attempted": out.attempted,
                "failed": out.failed, "ticks": w["ticks"],
                "admits": len(w["admit_s"]), "e2e": out.end_to_end,
                "ttft_p50_ms": ttft, "itl_mean_ms": w["itl_mean_ms"],
                "host": w["host"],
                "wall_s": time.time() - t0}), flush=True)
            if not ok:
                break
        held[rate] = ok
        if not ok:
            break
    knee = max((r for r in held if held[r]), default=None)
    print(json.dumps({"held": held, "knee": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
