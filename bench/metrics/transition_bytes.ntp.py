"""Bytes the reshard engine moved between ranks, as its ledger counts them
(``NTPSession.last_transition.bytes_moved``), summed over the window's
transitions."""


def read(run, records, summary):
    w = records.get("window")
    if not w or not w["transitions"]:
        return None
    return sum(t["bytes_moved"] for t in w["transitions"])
