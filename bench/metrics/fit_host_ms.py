"""Host time a fit adds outside the wait for the device: per fit, the
program's root span (``lamc``, or ``distributed_lamc`` on a mesh) less its
``wait`` span, averaged over the traced run's window. Nothing where the
program has no ``wait`` span under each root."""


def read(ctx):
    spans = ctx["spans"]
    roots = spans.get("lamc") or spans.get("distributed_lamc")
    waits = spans.get("wait")
    if ctx["kind"] != "fit" or not roots or not waits \
            or len(waits) != len(roots):
        return None
    return 1e3 * (sum(roots) - sum(waits)) / len(roots)
