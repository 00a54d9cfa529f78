"""Engine loop: host time of one ``step()``, the engine tracer's ``step``
span less its ``sync`` children (the wait for the device), mean over the
window's steps."""


def read(ctx):
    ms = ctx.host_step_ms
    return sum(ms) / len(ms) if ms else None
