"""Share of the busy device time in the traced window that falls under one
of the program's named scopes (``trace_scopes.layers``)."""
from bench import trace_scopes


def read(run, records, summary):
    if summary is None or "scopes" not in summary:
        return None
    return trace_scopes.layers(summary).get("scoped_share")
