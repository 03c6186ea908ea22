"""Share of the traced window in which the busiest chip ran no operation,
in a serve cell (``trace_reduce``: 1 - busy union / window)."""


def read(ctx):
    if ctx["kind"] != "serve" or ctx["trace"] is None:
        return None
    return 100.0 * ctx["trace"]["idle_share"]
