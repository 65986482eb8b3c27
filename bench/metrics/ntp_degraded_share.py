"""Tokens per second of the degraded steps over those of the healthy steps
before the failure, each step that follows a transition left out of both
(``phase_rates`` of the cell's driver). The paper's claim, 3 of 4 GPUs
working, predicts 75%."""


def read(run, records, summary):
    w = records.get("window")
    if not w:
        return None
    healthy, degraded = run.cell.driver.phase_rates(w)
    if not healthy or not degraded:
        return None
    return 100.0 * degraded / healthy
