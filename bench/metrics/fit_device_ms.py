"""Device busy time per fit: the busiest chip's union of operation
intervals over the traced window, divided by the fits traced (the trace
starts and stops between fits)."""


def read(ctx):
    red, fits = ctx["trace"], ctx["stats"].get("traced_fits", 0)
    if ctx["kind"] != "fit" or red is None or not fits:
        return None
    return 1e3 * red["busiest_busy_s"] / fits
