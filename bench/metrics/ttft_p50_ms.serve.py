"""Median milliseconds from a request's due time to its first token, over
the requests due in the window; none where that median is a request that
was refused or never served."""
import math
import statistics


def read(run, records, summary):
    xs = records.get("window", {}).get("ttft_s")
    if not xs:
        return None
    med = statistics.median(xs)
    return 1e3 * med if math.isfinite(med) else None
