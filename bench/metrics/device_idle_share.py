"""Device layer: share of the traced stretch with no operation on the
device, 1 - (union of operation intervals) / stretch (profiler trace)."""


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
