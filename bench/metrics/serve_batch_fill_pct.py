"""Mean fill of the service's batches over the window: coalesced rows over
the batch's capacity (``serve_svc_batch_fill_pct``)."""


def read(ctx):
    reg = ctx["registry"]
    h = (reg or {}).get("serve_svc_batch_fill_pct")
    if ctx["kind"] != "serve" or not h or not h["count"]:
        return None
    return h["sum"] / h["count"]
