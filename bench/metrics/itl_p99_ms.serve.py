"""The 99th percentile of the token gaps that end in the window, in
milliseconds: the gap of a step that also admits (PERF.md)."""


def read(run, records, summary):
    return records.get("window", {}).get("itl_p99_ms")
