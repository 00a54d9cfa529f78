"""Scheduler: mean rows per step from the engine's ``step.batch_size``
histogram over the window, as a share of ``max_slots``."""


def read(ctx):
    if ctx.batch_count <= 0:
        return None
    return 100.0 * ctx.batch_sum / ctx.batch_count / ctx.max_slots
