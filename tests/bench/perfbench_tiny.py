"""Tiny versions of the benchmark's cells, for driving the harness on the
CPU: the cells' own traffic files and drivers, with every size cut so that
a run takes seconds. The look for a chip is skipped; everything after it
runs as on the chip."""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

TINY_MODEL = {"hidden_size": 64, "intermediate_size": 256,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 300}
# the serving check reads gaps between the reference's best logit and the
# served token's: a vocabulary of some thousands has near-ties for the
# bfloat16 control to flip, as granite's 49k has
TINY_MODELS = {"granite-serve-chat": dict(TINY_MODEL, vocab_size=16384)}
TINY_TRAFFIC = {
    "granite-train": {"batch": 2, "seq": 32},
    # 4 slots of 64 positions, 10 requests a second, one second of warm-up
    "granite-serve-chat": {
        "slots": 4, "max_len": 64, "prefill_len": 32, "rate": 10.0,
        "warmup_s": 1.0,
        "prompt": {"median": 12, "sigma": 1.0, "min": 4, "max": 32},
        "output": {"median": 16, "sigma": 0.7, "min": 8, "max": 24},
        "check_requests": 6, "drain_s": 30.0},
}
# limits for the tiny CPU sizes, set from their readings: the program
# agrees with the reference to f32 rounding (loss 5e-7, gradient norms
# 8e-7, change 2e-5; every served token is the reference's best, mean
# gap 0, where the bfloat16 control reads 5.1e-5 to 1.1e-4 on five seeds)
TINY_LIMITS = {"loss_gap": 1e-4, "grad_norm_gap": 1e-4,
               "change_norm_gap": 1e-3, "served_logit_gap_mean": 1e-5}


CELLS = {  # workload: (chips, configuration, traffic mix)
    "granite-train": (1, "granite-3-2b-train", "train-2x4096"),
    "granite-serve-chat": (1, "granite-3-2b-serve", "serve-chat-poisson"),
}


def resized(sizes: dict, model: dict) -> dict:
    """``sizes`` with the model's sizes replaced; the attention scale the
    program runs follows the head size."""
    out = dict(sizes, **model)
    dep = dict(out["departures"])
    dep["attention_multiplier"] = dict(dep["attention_multiplier"],
                                       run=out["head_dim"] ** -0.5)
    out["departures"] = dep
    return out


def tiny_cell(workload: str, **traffic) -> harness.Cell:
    chips, config, mix = CELLS[workload]
    spec = harness.load_spec()
    cell = harness.make_cell(
        workload, chips, ROOT / "bench" / "configs" / f"{config}.json", mix,
        [m for m in spec["end_to_end"] if harness._reported_in(m, workload)],
        [m for m in spec["per_layer"] if harness._reported_in(m, workload)])
    cell.sizes = resized(cell.sizes, TINY_MODELS.get(workload, TINY_MODEL))
    cell.traffic = {**cell.traffic, **TINY_TRAFFIC[workload], **traffic}
    return cell


def tiny_run(workload: str, *, seed: int = 2**31 + 11, seconds: float = 2.0,
             limits=None, readings=False, devices=None, **traffic):
    import jax

    cell = tiny_cell(workload, **traffic)
    run = harness.Run(cell=cell, seed=seed, seconds=seconds, trace=False,
                      devices=devices or jax.devices()[: cell.chips],
                      meter=harness.CompileMeter(),
                      limits=limits or TINY_LIMITS, readings=readings)
    return run, cell.driver.run(run)
