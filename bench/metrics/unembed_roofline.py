"""Unembedding: device self time of the ``unembed`` scope (the sampled
rows' vocabulary projection; profiler trace) against the roofline of its
part of each step's count (bench/parts.py)."""

from bench.parts import roofline_share


def read(ctx):
    return roofline_share(ctx, "unembed")
