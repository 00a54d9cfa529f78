"""Lightweight step tracing: Chrome trace-event JSON for Perfetto.

The engine brackets its phases (``plan`` / ``admit`` / ``dispatch`` /
``sync`` / ``harvest``, nested under a per-iteration ``step`` span) with
``tracer.span(...)`` context managers.  ``ChromeTracer`` records each as a
complete ("X") event — begin timestamp plus duration in microseconds on one
thread track, which Perfetto nests by containment — plus optional instant
("i") and counter ("C") events for pool occupancy tracks.  The output of
``save()``/``to_json()`` is the standard Trace Event Format object
(``{"traceEvents": [...]}``) loadable at https://ui.perfetto.dev.

Every enabled span also enters a ``jax.profiler.TraceAnnotation`` of the
same name (``StepTraceAnnotation`` with ``step_num`` for ``step``), so
when a ``jax.profiler`` trace is active the engine's phases land on a host
line of its ``.xplane.pb``, on the clock of the device's operations.  The
JSON events are unchanged by it.  ``dispatch`` carries the step's
composition in its args, as the mixed step lays it out (a decode group of
one position per slot, and a span group of ``rows`` prefill rows each
``bucket`` positions wide; ``serving/engine.py`` ``_mixed_step_body``):

* ``bucket``: the span group's width S, a power of two; 1 for a
  decode-only step;
* ``rows``: the span group's row count R, the power-of-two bucket of
  ``prefill_rows`` (at most the slot count), 0 for a decode-only step;
* ``positions``: what the step computes, slots + R x S, padding included
  (the engine sums it in ``stats["step_positions"]``);
* ``prefill_rows`` / ``decode_rows``: the rows that carry a real span.

Inside the jitted mixed step the layers carry ``jax.named_scope`` names,
which reach each compiled operation's HLO ``op_name`` metadata and the
profiler's view of that operation.  An operation belongs to the innermost
of these scopes; the names are a contract the benchmark's trace reduction
reads (``bench/scopes.py``):

=============  ======================================  =====================
scope          where                                   covers
=============  ======================================  =====================
``linear``     ``core/linear.py`` ``linear_apply``     every projection:
                                                       Monarch einsum or
                                                       Pallas, quantized,
                                                       dense
``attention``  ``models/layers.py``                    RoPE, the KV page
               ``attention_apply``                     write, the gather or
                                                       span kernel, scores,
                                                       softmax, AV
``unembed``    ``models/layers.py`` ``unembed``        vocabulary projection
``embed``      ``models/layers.py`` ``embed``          token embedding
``norm``       ``models/layers.py`` ``norm_apply``     RMSNorm / LayerNorm
``sample``     ``serving/engine.py``                   the greedy or
               ``_mixed_step_body``                    temperature draw and
                                                       its token/key updates
=============  ======================================  =====================

Scopes are metadata only: they change no compiled operation.  So JAX's
persistent compilation cache, whose key leaves metadata out, may hand back
an executable compiled from the same program without them; a trace that
must show them sets ``jax_compilation_cache_include_metadata_in_key``.

When tracing is off the engine holds the module-level ``NULL_TRACER``:
``span()`` returns one reusable no-op context manager and the counter/
instant hooks return immediately, so the instrumented hot path costs a
single attribute call per phase — near-zero overhead by construction, no
``if tracing:`` forests at the call sites.

``validate_trace`` is the schema check CI and the tests run against the
emitted JSON: every event must carry the trace-event required fields
(``ph``/``name``/``ts``/``pid``/``tid``, ``dur`` for "X"), which is what
"loads in Perfetto" means mechanically.
"""

from __future__ import annotations

import json
import time
from typing import Optional

import jax

_VALID_PHASES = {"X", "B", "E", "i", "I", "C", "M"}


class _Span:
    """One timed section; appends a complete ("X") event on exit, and runs
    inside a profiler annotation of the same name (a step annotation for
    ``step``), so an active ``jax.profiler`` trace shows it on the host
    line beside the device's operations."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_annotation")

    def __init__(self, tracer: "ChromeTracer", name: str, args: dict):
        self._tracer = tracer
        self._name = name
        self._args = args

    def note(self, **args) -> None:
        """Add args known only once the section has run."""
        self._args.update(args)

    def __enter__(self) -> "_Span":
        if self._name == "step" and "step" in self._args:
            self._annotation = jax.profiler.StepTraceAnnotation(
                "step", step_num=self._args["step"])
        else:
            self._annotation = jax.profiler.TraceAnnotation(self._name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        t1 = time.perf_counter()
        self._annotation.__exit__(exc_type, exc, tb)
        tr = self._tracer
        ev = {"name": self._name, "ph": "X", "pid": tr.pid, "tid": tr.tid,
              "ts": (self._t0 - tr._epoch) * 1e6,
              "dur": (t1 - self._t0) * 1e6}
        if self._args:
            ev["args"] = self._args
        tr._events.append(ev)


class _NullSpan:
    """Reusable no-op context manager (one shared instance)."""

    __slots__ = ()

    def note(self, **args) -> None:
        return None

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return None


_NULL_SPAN = _NullSpan()


class ChromeTracer:
    """Collects Chrome trace events; ``save()`` writes Perfetto-ready JSON."""

    enabled = True

    def __init__(self, path: Optional[str] = None, pid: int = 0, tid: int = 0,
                 process_name: str = "serving-engine"):
        self.path = path
        self.pid = pid
        self.tid = tid
        self._epoch = time.perf_counter()
        self._events: list[dict] = [{
            "name": "process_name", "ph": "M", "pid": pid, "tid": tid,
            "args": {"name": process_name}}]

    def span(self, name: str, **args) -> _Span:
        """Context manager timing one section: ``with tracer.span("plan"):``.
        Keyword args land in the event's ``args`` (visible on click in
        Perfetto)."""
        return _Span(self, name, args)

    def instant(self, name: str, **args) -> None:
        ev = {"name": name, "ph": "i", "pid": self.pid, "tid": self.tid,
              "ts": (time.perf_counter() - self._epoch) * 1e6, "s": "t"}
        if args:
            ev["args"] = args
        self._events.append(ev)

    def counter(self, name: str, **values) -> None:
        """Counter track (e.g. pool occupancy over time)."""
        self._events.append({
            "name": name, "ph": "C", "pid": self.pid, "tid": self.tid,
            "ts": (time.perf_counter() - self._epoch) * 1e6, "args": values})

    @property
    def events(self) -> list[dict]:
        return self._events

    def span_counts(self) -> dict[str, int]:
        """How many completed spans were recorded per name (CI asserts the
        plan/dispatch/harvest coverage of every engine iteration on this)."""
        out: dict[str, int] = {}
        for ev in self._events:
            if ev["ph"] == "X":
                out[ev["name"]] = out.get(ev["name"], 0) + 1
        return out

    def to_json(self) -> dict:
        return {"traceEvents": self._events, "displayTimeUnit": "ms"}

    def save(self, path: Optional[str] = None) -> str:
        path = path or self.path
        if not path:
            raise ValueError("no trace output path given")
        with open(path, "w") as f:
            json.dump(self.to_json(), f)
        return path


class NullTracer:
    """No-op tracer: one shared null span, empty event list."""

    enabled = False
    events: list = []

    def span(self, name: str, **args) -> _NullSpan:
        return _NULL_SPAN

    def instant(self, name: str, **args) -> None:
        return None

    def counter(self, name: str, **values) -> None:
        return None

    def span_counts(self) -> dict:
        return {}

    def to_json(self) -> dict:
        return {"traceEvents": [], "displayTimeUnit": "ms"}

    def save(self, path: Optional[str] = None) -> str:
        raise ValueError("tracing was not enabled: nothing to save")


NULL_TRACER = NullTracer()


def validate_trace(trace) -> int:
    """Check Trace Event Format conformance; returns the event count.

    Accepts the object form (``{"traceEvents": [...]}``) or a bare event
    list.  Raises ``ValueError`` on the first malformed event — this is the
    machine-checkable version of "the trace loads in Perfetto".
    """
    if isinstance(trace, dict):
        events = trace.get("traceEvents")
        if not isinstance(events, list):
            raise ValueError("trace object lacks a traceEvents list")
    elif isinstance(trace, list):
        events = trace
    else:
        raise ValueError(f"not a trace: {type(trace).__name__}")
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            raise ValueError(f"event {i} is not an object")
        ph = ev.get("ph")
        if ph not in _VALID_PHASES:
            raise ValueError(f"event {i} has invalid phase {ph!r}")
        if not isinstance(ev.get("name"), str):
            raise ValueError(f"event {i} lacks a name")
        if "pid" not in ev or "tid" not in ev:
            raise ValueError(f"event {i} lacks pid/tid")
        if ph != "M":
            ts = ev.get("ts")
            if not isinstance(ts, (int, float)) or ts < 0:
                raise ValueError(f"event {i} has invalid ts {ts!r}")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                raise ValueError(f"event {i} has invalid dur {dur!r}")
    return len(events)


def load_trace(path: str) -> list[dict]:
    """Load + validate a saved trace file; returns its event list."""
    with open(path) as f:
        trace = json.load(f)
    validate_trace(trace)
    return trace["traceEvents"] if isinstance(trace, dict) else trace


__all__ = ["ChromeTracer", "NullTracer", "NULL_TRACER", "validate_trace",
           "load_trace"]
