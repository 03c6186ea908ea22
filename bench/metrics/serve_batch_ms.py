"""Mean time the service spent on one coalesced batch over the window
(``serve_svc_batch_latency_us``: zero-fill, copy to the device, score,
fence)."""


def read(ctx):
    reg = ctx["registry"]
    h = (reg or {}).get("serve_svc_batch_latency_us")
    if ctx["kind"] != "serve" or not h or not h["count"]:
        return None
    return h["sum"] / h["count"] / 1e3
