"""Median host milliseconds from a request's due time to the start of its
admit, over the requests due in the window."""
import statistics


def read(run, records, summary):
    xs = records.get("window", {}).get("queue_wait_s")
    return 1e3 * statistics.median(xs) if xs else None
