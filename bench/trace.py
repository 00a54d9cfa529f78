"""From a profiler trace to device numbers.

``bench.scopes.load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
into plain lists of ``(name, start_ns, duration_ns)``: the device's
operations (line "XLA Ops" of each ``/device:`` plane) and the harness's
host spans (its ``TraceAnnotation`` names ``HOST_SPANS``, on any host
line).  ``reduce`` turns them into:

* the stretch: from the first to the last end of a harness span;
* ``busy_s``: the union of the device's operation intervals inside it,
  averaged over the device planes; the idle share is 1 - busy / stretch;
* ``device_ops``: self time by operation (time in which the operation ran
  and none nested in it did; a ``while`` that holds a scanned layer keeps
  only its own gaps), named ``<instruction> <opcode>`` as the profiler
  prints the HLO, most first;
* ``idle_gaps``: the longest stretches with no operation on the device,
  each named by the innermost harness span that covers its middle.
"""

from __future__ import annotations

import re
from typing import Optional

OPS_LINE = "XLA Ops"
HOST_SPANS = ("engine.step", "add_request", "wait_due")
TOP = 10


def profiler_options():
    """No Python tracer (it records every Python call and slows the host
    many times over); host spans at the level of ``TraceAnnotation``."""
    import jax

    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 1
    return o


def short_name(hlo: str) -> str:
    """``%fusion.12 = bf16[..] fusion(...)`` -> ``fusion.12 fusion``."""
    m = re.match(r"%?([\w.\-]+) = .*? ([\w\-]+)\(", hlo)
    return f"{m.group(1)} {m.group(2)}" if m else hlo[:64]


def self_times(ops, lo: float, hi: float) -> dict:
    """Time by operation name inside [lo, hi), less what nested operations
    cover (operations on one line nest, as a loop holds its body)."""
    out: dict = {}
    stack: list = []
    for name, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        s, e = max(s, lo), min(s + d, hi)
        if e <= s:
            continue
        while stack and stack[-1][1] <= s:
            stack.pop()
        out[name] = out.get(name, 0.0) + (e - s)
        if stack:
            parent = stack[-1]
            out[parent[0]] -= min(e, parent[1]) - s
        stack.append((name, e))
    return out


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce(events: dict) -> Optional[dict]:
    """Stretch, device busy time, top operations and labelled idle gaps;
    None when the trace holds no device operation or no harness span."""
    host, devices = events["host"], events["devices"]
    if not host or not devices:
        return None
    lo = min(s for _, s, _ in host)
    hi = max(s + d for _, s, d in host)
    busy, by_op, first = [], {}, None
    for ops in devices:
        merged = union(((s, s + d) for _, s, d in ops), lo, hi)
        busy.append(sum(e - s for s, e in merged))
        if first is None:
            first = merged
        for name, v in self_times(ops, lo, hi).items():
            by_op[name] = by_op.get(name, 0.0) + v
    busy_ns = sum(busy) / len(busy)
    if busy_ns <= 0:
        return None
    n_dev = len(devices)
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    gaps, prev = [], lo
    for s, e in first + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "device_ops": [[n, v * 1e-9 / n_dev] for n, v in top_ops],
        "idle_gaps": [[_label(host, (a + b) / 2), (b - a) * 1e-9]
                      for a, b in gaps[:TOP]],
    }


def _label(host, t: float) -> str:
    """The innermost (shortest) harness span covering time ``t``."""
    best = None
    for name, s, d in host:
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "outside harness spans"
