"""Median host milliseconds of one ``ServeEngine.admit`` that ended in the
window: the padded prefill, the wait for its first token, and the dispatch
of the slot-cache insert."""
import statistics


def read(run, records, summary):
    xs = records.get("window", {}).get("admit_s")
    return 1e3 * statistics.median(xs) if xs else None
