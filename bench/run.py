"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration,
traffic mix, driver and metric readers are files under ``bench/``. The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or its per-layer
metrics with ``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``,
and last ``checks``: each number compared with the reference beside its
limit. The same comparisons are the last lines of standard error.

Without an accelerator, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

T_PROCESS = harness.process_start_time()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    devices = harness.accelerator_devices(cell.chips)
    harness.configure_compile_cache()
    run = harness.Run(cell=cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), devices=devices,
                      t_process=T_PROCESS, meter=harness.CompileMeter())
    outcome = cell.driver.run(run)
    summary = None
    if run.trace:
        from bench import trace_reduce

        if run.trace_path is None:
            raise SystemExit("bench: the profiler wrote no trace")
        summary = trace_reduce.reduce(run.trace_path)
        harness.shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    line = harness.result_line(run, outcome, summary)
    harness.print_checks(outcome.checks)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
