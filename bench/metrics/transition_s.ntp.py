"""Seconds from each event of the window to the end of the first step in
the new layout: the ``session.transition`` span (plan, state to the host,
repack, state to the devices), the step program's rebuild and that step,
summed over the window's transitions."""


def read(run, records, summary):
    w = records.get("window")
    if not w or not w["transitions"]:
        return None
    return sum(t["t_first_step"] - t["t_event"] for t in w["transitions"])
