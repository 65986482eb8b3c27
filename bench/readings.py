"""Read a cell's compared numbers over many seeds in one process: the
program's (the lower readings of each limit) and, with ``--control``, those
of the control and of the planted faults against the reference (the upper
readings). With ``--fault NAME`` every run has that fault of the driver's
``FAULTS`` planted in the program, and its numbers are upper readings. The
benchmark's own runs never do this. A driver whose check needs no window
(``ntp_fail_repair``) runs none at ``--seconds 0``.

    python3 bench/readings.py --workload <name> --seeds 1,2,3 --seconds 5 \
        [--control] [--fault NAME]

One JSON line per seed, in which each control or fault reading is also
judged by the harness's ``Check`` against the cell's limits
(``bench/limits/<cell>.json``, where it exists): ``fails`` names the
numbers it fails. Then a line with the largest program reading and the
smallest control and fault readings of each number. A reading whose name
begins with ``program`` (the program against another reference) is a lower
reading.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default=None)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    devices = harness.accelerator_devices(cell.chips)
    harness.configure_compile_cache()
    meter = harness.CompileMeter()
    limits_file = harness.BENCH / "limits" / f"{cell.name}.json"
    limits = (json.loads(limits_file.read_text())["limits"]
              if limits_file.is_file() else {})
    inf = float("inf")
    prog, upper = {}, {}
    planted = (cell.driver.FAULTS[args.fault] if args.fault
               else contextlib.nullcontext)
    for seed in (int(x) for x in args.seeds.split(",")):
        t0 = time.time()
        run = harness.Run(cell=cell, seed=seed, seconds=args.seconds,
                          trace=False, devices=devices, t_process=t0,
                          meter=meter, limits=_AnyLimit(),
                          readings=args.control)
        with planted():
            out = cell.driver.run(run)
        nums = {c.name: c.value for c in out.checks}
        reads = dict(out.records.get("readings") or {})
        if args.fault:
            reads[args.fault] = nums
        else:
            for k, v in nums.items():
                prog[k] = max(prog.get(k, 0.0), v)
        fails = {}
        for fault, vals in reads.items():
            for k, v in vals.items():
                key = f"{fault}.{k}"
                if fault.startswith("program"):
                    prog[key] = max(prog.get(key, 0.0), v)
                else:
                    upper[key] = min(upper.get(key, inf), v)
            fails[fault] = [k for k, v in vals.items() if k in limits
                            and not harness.Check(k, v, limits[k]).ok]
        print(json.dumps({"seed": seed, "program": nums, "readings": reads,
                          "fails": fails,
                          "e2e": out.end_to_end, "wall_s": time.time() - t0}),
              flush=True)
    print(json.dumps({"lower": prog, "upper": upper}), flush=True)
    return 0


class _AnyLimit(dict):
    """Limits of infinity: readings are taken before limits exist."""

    def __getitem__(self, key):
        return float("inf")


if __name__ == "__main__":
    sys.exit(main())
