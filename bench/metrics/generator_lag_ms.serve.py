"""Mean host milliseconds by which the load generator submitted a request
after it was due, over the requests due in the window."""
import statistics


def read(run, records, summary):
    xs = records.get("window", {}).get("generator_lag_s")
    return 1e3 * statistics.fmean(xs) if xs else None
