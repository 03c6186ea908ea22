"""Host time of the fit program's call up to its return: the mean of the
program's ``dispatch`` spans (``repro.obs``) over the traced run's window.
Nothing where the program has no such span."""


def read(ctx):
    spans = ctx["spans"].get("dispatch")
    if ctx["kind"] != "fit" or not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
