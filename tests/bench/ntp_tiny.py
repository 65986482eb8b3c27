"""A tiny version of the NTP fail/repair cell, for driving its harness on
four virtual CPU devices: the cell's own configuration, traffic file and
driver, with every size cut so that a run takes seconds. The look for a
chip is skipped; everything after it runs as on the chip.

Run as a script, it drives a sound run (with the control's reading) and a
run with each planted fault, and prints one JSON line of what the tests
read:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 tests/bench/ntp_tiny.py
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

CELL = "ntp-granite-2x2-failrepair"
CONFIG, TRAFFIC = "granite-3-2b-ntp", "ntp-2x2-failrepair"
TINY_MODEL = {"hidden_size": 64, "intermediate_size": 128,
              "mlp_unit_rows": 32, "num_attention_heads": 8,
              "num_key_value_heads": 4, "head_dim": 16,
              "num_hidden_layers": 2, "vocab_size": 300}
TINY_TRAFFIC = {"local_batch": 4, "seq": 32, "in_flight": 2,
                "min_degraded_steps": 2}
# limits for the tiny CPU size, set from its readings: the program agrees
# with the reference to float32 rounding (loss 1e-6, gradient norms 1e-6,
# change 1e-4 on five seeds), where the bfloat16 control reads a change gap
# near 1 and each planted fault 3e-3 or more in one number
TINY_LIMITS = {"loss_gap": 1e-4, "grad_norm_gap": 1e-4,
               "change_norm_gap": 1e-3}


def tiny_cell() -> harness.Cell:
    spec = harness.load_spec()
    cell = harness.make_cell(
        CELL, 4, ROOT / "bench" / "configs" / f"{CONFIG}.json", TRAFFIC,
        [m for m in spec["end_to_end"] if harness._reported_in(m, CELL)],
        [m for m in spec["per_layer"] if harness._reported_in(m, CELL)])
    sizes = dict(cell.sizes, **TINY_MODEL)
    dep = dict(sizes["departures"])
    dep["attention_multiplier"] = dict(dep["attention_multiplier"],
                                       run=sizes["head_dim"] ** -0.5)
    sizes["departures"] = dep
    cell.sizes = sizes
    cell.traffic = {**cell.traffic, **TINY_TRAFFIC}
    return cell


def tiny_run(*, seed: int = 2**31 + 13, seconds: float = 10.0,
             limits=None, readings: bool = False):
    import jax

    cell = tiny_cell()
    run = harness.Run(cell=cell, seed=seed, seconds=seconds, trace=False,
                      devices=jax.devices()[: cell.chips],
                      meter=harness.CompileMeter(),
                      limits=limits or TINY_LIMITS, readings=readings)
    return run, cell.driver.run(run)


def _summary(run, out) -> dict:
    win = out.records["window"]
    return {
        "correct": harness.result_line(run, out, None)["correct"],
        "checks": {c.name: c.value for c in out.checks},
        "end_to_end": out.end_to_end, "attempted": out.attempted,
        "seconds": run.seconds, "window_s": win["window_s"],
        "transitions": win["transitions"],
        "plans": [list(tp) for tp, _, _ in win["steps_run"]],
        "check_plans": [list(p) for p in
                        out.records["check"]["program"]["plans"]],
        "readings": out.records.get("readings", {}),
    }


def main() -> int:
    import jax

    if len(jax.devices()) < 4:
        raise SystemExit("needs four devices: XLA_FLAGS="
                         "--xla_force_host_platform_device_count=4")
    out = {"sound": _summary(*tiny_run(readings=True)), "faults": {}}
    faults = tiny_cell().driver.FAULTS
    for name, plant in faults.items():
        with plant():
            out["faults"][name] = _summary(*tiny_run())
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
