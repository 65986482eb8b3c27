"""The serving step's share of the chips' peak: the model's operations for
the real prompt tokens prefilled and the served tokens decoded in the
window, each at its position (``prefill_flops``, ``serve_token_flops``),
over the window's seconds times the chips times one chip's bf16 peak.
Padding, and decodes whose token is never served, do not count."""
from bench import harness


def read(run, records, summary):
    w = records.get("window")
    if not w or not (w["prefill_lengths"] or w["decode_positions"]):
        return None
    C, s = run.cell.config, run.cell.sizes
    flops = sum(C.prefill_flops(s, n) for n in w["prefill_lengths"])
    flops += sum(C.serve_token_flops(s, p, head=True)
                 for p in w["decode_positions"])
    peak = harness.device_peaks(run.devices[0].device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / (w["window_s"] * len(run.devices) * peak)
