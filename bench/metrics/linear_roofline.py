"""Monarch linears: device self time of the ``linear`` scope (every
projection; profiler trace) against the roofline of its part of each
step's count (bench/parts.py)."""

from bench.parts import roofline_share


def read(ctx):
    return roofline_share(ctx, "linear")
