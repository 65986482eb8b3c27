"""The whole window's share of the chips' peak: goodput (tokens of the
rows that train, over the window's seconds, the transitions' stalls
included) times the model's operations per token, over the chips times
one chip's bf16 peak. Attention counted causal, once; no recomputation,
nor the head's second copy in a scale-up domain."""
from bench import harness


def read(run, records, summary):
    w = records.get("window")
    if not w or not w["window_s"]:
        return None
    flops = run.cell.config.train_flops_per_token(run.cell.sizes,
                                                  run.cell.traffic["seq"])
    peak = harness.device_peaks(run.devices[0].device_kind)["bf16_flops_per_s"]
    return 100.0 * w["tokens"] / w["window_s"] * flops / (len(run.devices)
                                                         * peak)
