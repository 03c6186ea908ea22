"""Time per fit that the busiest chip spent in collectives (all-to-all,
all-gather, all-reduce, reduce-scatter, collective-permute), from the
profiler trace; nothing on a single chip."""


def read(ctx):
    red, fits = ctx["trace"], ctx["stats"].get("traced_fits", 0)
    if ctx["kind"] != "fit" or red is None or not fits or red["devices"] < 2:
        return None
    return 1e3 * red["busiest_collective_s"] / fits
