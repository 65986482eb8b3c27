"""The whole training step's share of the chips' peak: tokens per second
of the window times the model's operations per token, over the chips times
one chip's bf16 peak. Attention counted causal, once; no recomputation."""
from bench import harness


def read(run, records, summary):
    w = records["window"]
    if not w["window_s"]:
        return None
    seq = run.cell.traffic["seq"]
    flops = run.cell.config.train_flops_per_token(run.cell.sizes, seq)
    peak = harness.device_peaks(run.devices[0].device_kind)["bf16_flops_per_s"]
    return 100.0 * w["tokens"] / w["window_s"] * flops / (len(run.devices)
                                                         * peak)
