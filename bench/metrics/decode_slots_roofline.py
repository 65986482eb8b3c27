"""The slot decode program's share of the HBM roofline: the bytes one tick
must read (``decode_tick_bytes``: weights, head, and the K and V of the
live positions, averaged over the window's ticks) over the program's device
time per run in the traced window times one chip's HBM bandwidth."""
import statistics

from bench import harness

MODULE = "decode_slots"


def read(run, records, summary):
    w = records.get("window") or {}
    if summary is None or not w.get("tick_live_positions"):
        return None
    names = [k for k in summary.get("modules", {}) if MODULE in k]
    calls = sum(summary.get("module_calls", {}).get(k, 0) for k in names)
    device_s = sum(summary["modules"][k] for k in names)
    if not calls or not device_s:
        return None
    s = run.cell.sizes
    itemsize = 4 if s["param_dtype"] == "float32" else 2
    need = run.cell.config.decode_tick_bytes(
        s, statistics.fmean(w["tick_live_positions"]), itemsize)
    bw = harness.device_peaks(run.devices[0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / (device_s / calls * bw)
