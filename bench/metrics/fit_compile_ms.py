"""Host time per fit spent tracing, lowering, compiling or loading a
program from the persistent cache inside the window: the sum of the
program's ``compile`` spans over the fits. 0.0 when nothing recompiles.
The program that records ``compile`` spans also records a ``dispatch``
span per fit; where it has none, nothing is read."""


def read(ctx):
    spans, fits = ctx["spans"], ctx["stats"].get("fits", 0)
    if ctx["kind"] != "fit" or not spans.get("dispatch") or not fits:
        return None
    return 1e3 * sum(spans.get("compile", ())) / fits
