"""Share of the traced window in which no operation ran on the device,
averaged over the cell's devices; the transitions' host work is most of
it."""


def read(run, records, summary):
    if summary is None or summary.get("idle_share") is None:
        return None
    return 100.0 * summary["idle_share"]
