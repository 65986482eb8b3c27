"""Device milliseconds per training step under the named scope ``loss_head``,
all passes, in the traced window (``trace_scopes.layers``)."""
from bench import trace_scopes


def read(run, records, summary):
    if summary is None or "scopes" not in summary:
        return None
    return trace_scopes.layers(summary).get("loss_head_ms")
