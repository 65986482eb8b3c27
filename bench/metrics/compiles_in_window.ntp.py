"""Programs built in the window that JAX's persistent cache did not hold:
its compile-or-load events less its cache hits. Set-up's check cycle
builds both layouts' programs, so the window should load, not compile."""


def read(run, records, summary):
    w = records.get("window")
    return None if not w else w["compiles"]
