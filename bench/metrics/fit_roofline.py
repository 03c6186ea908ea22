"""The fit program's share of its roofline: the least time the work that a
fit requires could take on this chip (``counts.lamc_fit`` over
``peaks.least_seconds``), over the device busy time per fit."""

import peaks


def read(ctx):
    red, fits = ctx["trace"], ctx["stats"].get("traced_fits", 0)
    if ctx["kind"] != "fit" or red is None or not fits or ctx["work"] is None:
        return None
    device_s = red["busiest_busy_s"] / fits
    if device_s <= 0:
        return None
    flops, nbytes = ctx["work"]
    return 100.0 * peaks.least_seconds(flops, nbytes, ctx["device_kind"]) / device_s
