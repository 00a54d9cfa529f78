"""Whole mixed step against the chip's peak: the sum over the stretch's
steps of max(F / peak FLOP/s, B / peak bytes/s), with F and B counted from
what each step had to do (bench/work.py), over the stretch's length."""


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.stretch_work:
        return None
    p = ctx.peaks
    need = sum(max(f / p["bf16_flops_per_s"], b / p["hbm_bytes_per_s"])
               for f, b in ctx.stretch_work)
    return 100.0 * need / t["window_s"]
