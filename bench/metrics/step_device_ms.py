"""Mixed step: device-busy time of the traced stretch per engine step
(profiler trace; independent of operation and module names)."""


def read(ctx):
    t = ctx.trace
    if t is None or ctx.stretch_steps <= 0:
        return None
    return 1e3 * t["busy_s"] / ctx.stretch_steps
