"""Host time of the plan search per fit: the mean of the program's ``plan``
spans (``repro.obs``) over the traced run's window."""


def read(ctx):
    spans = ctx["spans"].get("plan")
    if ctx["kind"] != "fit" or not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
