"""Device time by the program's own layers, and idle gaps by its phases.

``bench/trace.py`` reduces a profiler trace under the HLO's instruction
names and the harness's three host spans.  This module adds what the
program's names give (``serving/tracing.py`` states them as a contract):

* each device operation's scope: the innermost of ``SCOPES`` in the
  ``/``-separated HLO ``op_name`` that the trace carries for it (the
  ``OP_NAME_STAT`` stat of its event's metadata), ``-`` for none;
* the program's host spans ``PROGRAM_SPANS`` (the engine's tracer, on the
  profiler's clock when tracing is on) and XLA's host compile events.

``reduce`` returns ``bench.trace.reduce``'s numbers over the same stretch,
which the harness's spans alone bound, so ``window_s`` and ``busy_s`` read
exactly as there, and besides:

* ``device_ops`` named ``<instruction> <opcode> @<scope>``;
* ``scope_s``: device self time by scope over the stretch, which adds up
  to the busy time when operations nest as a loop holds its body;
* ``idle_gaps`` each named by the innermost harness, program or compile
  span that covers its middle.
"""

from __future__ import annotations

import glob
import os
from typing import Optional

from bench import trace as TR

SCOPES = ("linear", "attention", "unembed", "embed", "norm", "sample")
PROGRAM_SPANS = ("step", "plan", "admit", "dispatch", "sync", "harvest")
OP_NAME_STAT = "tf_op"
NO_SCOPE = "-"


def scope_of(op_name: str) -> str:
    """Innermost program scope of an HLO ``op_name`` path (a trace's
    ``tf_op`` appends ``:<op type>``, empty for JAX)."""
    path = op_name.rsplit(":", 1)[0].split("/")
    return next((p for p in reversed(path) if p in SCOPES), NO_SCOPE)


def is_compile(name: str) -> bool:
    """XLA's host events for compiling a program (their names say so)."""
    return "compile" in name.lower()


def load(logdir: str) -> dict:
    """``bench.trace.load``'s lists, the device operations' names carrying
    their scope, plus ``program``: the program's spans and XLA's compile
    events on any host line, as ``(name, start_ns, duration_ns)``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(paths[-1])
    op_name = op_names(paths[-1])
    devices, host, program = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            names = op_name.get(plane.name, {})
            ops = [(f"{TR.short_name(e.name)} @"
                    f"{scope_of(names.get(e.name, ''))}",
                    e.start_ns, e.duration_ns)
                   for line in plane.lines if line.name == TR.OPS_LINE
                   for e in line.events]
            if ops:
                devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    span = (e.name, e.start_ns, e.duration_ns)
                    if e.name in TR.HOST_SPANS:
                        host.append(span)
                    elif e.name in PROGRAM_SPANS or is_compile(e.name):
                        program.append(span)
    return {"devices": devices, "host": host, "program": program}


# The op_name of a device operation is a stat of its event's metadata,
# which ``ProfileData`` does not expose; it is read here from the XSpace
# proto's wire format (tsl/profiler/protobuf/xplane.proto):
#   XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map),
#   .stat_metadata = 5 (map); map entry key = 1, value = 2;
#   XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1,
#   .str_value = 5, .ref_value = 7; XStatMetadata.name = 2.


def _varint(buf, pos: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf, pos: int = 0, end: Optional[int] = None):
    """(field number, value) of one message: an int for varints, a
    (start, end) slice for length-delimited fields; fixed-width skipped."""
    end = len(buf) if end is None else end
    while pos < end:
        key, pos = _varint(buf, pos)
        kind = key & 7
        if kind == 0:
            v, pos = _varint(buf, pos)
            yield key >> 3, v
        elif kind == 2:
            n, pos = _varint(buf, pos)
            yield key >> 3, (pos, pos + n)
            pos += n
        elif kind in (1, 5):
            pos += 8 if kind == 1 else 4
        else:
            raise ValueError(f"unsupported wire type {kind} in xplane")


def _map_entries(buf, span):
    key, value = 0, None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def op_names(path: str) -> dict:
    """{device plane name: {operation's event name: HLO op_name}} from the
    ``OP_NAME_STAT`` stat of each event metadata of the device planes."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for g, v in _fields(buf, *plane):
            if g == 2:
                name = bytes(buf[v[0]:v[1]]).decode()
                if not name.startswith("/device:"):
                    break
            elif g == 4:
                events.append(_map_entries(buf, v)[1])
            elif g == 5:
                key, meta = _map_entries(buf, v)
                for h, w in _fields(buf, *meta):
                    if h == 2:
                        stat_names[key] = bytes(buf[w[0]:w[1]]).decode()
        if not name.startswith("/device:"):
            continue
        table = {}
        for ev in events:
            ev_name, op = "", ""
            for g, v in _fields(buf, *ev):
                if g == 2:
                    ev_name = bytes(buf[v[0]:v[1]]).decode()
                elif g == 5:
                    stat_id, value = 0, ""
                    for h, w in _fields(buf, *v):
                        if h == 1:
                            stat_id = w
                        elif h == 5:
                            value = bytes(buf[w[0]:w[1]]).decode()
                        elif h == 7:    # interned: a stat metadata's name
                            value = stat_names.get(w, "")
                    if stat_names.get(stat_id) == OP_NAME_STAT:
                        op = value
            if op:
                table[ev_name] = op
        out[name] = table
    return out


def reduce(events: dict) -> Optional[dict]:
    """``bench.trace.reduce`` of the same events, with scoped operation
    names, device self time by scope, and idle gaps labelled by the
    innermost harness or program span; None where that gives None."""
    out = TR.reduce(events)
    if out is None:
        return None
    host, devices = events["host"], events["devices"]
    lo = min(s for _, s, _ in host)
    hi = max(s + d for _, s, d in host)
    by_scope: dict = {}
    for ops in devices:
        for name, v in TR.self_times(ops, lo, hi).items():
            scope = name.rpartition(" @")[2] if " @" in name else NO_SCOPE
            by_scope[scope] = by_scope.get(scope, 0.0) + v
    ranked = sorted(by_scope.items(), key=lambda kv: -kv[1])
    out["scope_s"] = {k: v * 1e-9 / len(devices) for k, v in ranked}
    # the gaps bench.trace.reduce finds, in its order, labelled anew
    spans = list(host) + list(events.get("program", ()))
    busy = TR.union(((s, s + d) for _, s, d in devices[0]), lo, hi)
    gaps, prev = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    out["idle_gaps"] = [[TR._label(spans, (a + b) / 2), (b - a) * 1e-9]
                        for a, b in gaps[:TR.TOP]]
    return out
