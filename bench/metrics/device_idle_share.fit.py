"""Share of the traced window in which the busiest chip ran no operation,
in a fit cell (``trace_reduce``: 1 - busy union / window)."""


def read(ctx):
    if ctx["kind"] != "fit" or ctx["trace"] is None:
        return None
    return 100.0 * ctx["trace"]["idle_share"]
