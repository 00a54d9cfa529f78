"""The measured window: drive ``ContinuousBatchingEngine.add_request`` and
``step()`` with a mix's requests, as a client would, and record what the
client sees.

* A request is added when it falls due (an open loop), or all at once (a
  backlog).  Its TTFT counts from its due time, not from ``add_request``.
* Token times are stamped in the engine's ``on_token`` callback, which the
  engine calls at harvest, after the device sync.
* Warm-up runs the mix unmeasured for ``warmup_s`` seconds (an open loop,
  whose arrivals run on the clock) or ``warmup_steps`` engine steps (a
  backlog: the window then opens at the same point of the same work for a
  given seed); the window is the next ``seconds``.  A request due in the window with no first token at
  its end counts with TTFT = end - due (a lower bound), so a stall shows in
  the tail.
* Compilations inside the window are counted (there should be none).
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Optional

import numpy as np

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


@dataclasses.dataclass
class Record:
    due: float                 # absolute host time the request fell due
    added: float = -1.0
    req: object = None         # the engine's Request
    tokens: list = dataclasses.field(default_factory=list)
    times: list = dataclasses.field(default_factory=list)
    error: Optional[str] = None


class CompileCounter:
    """Counts compilations: programs that went to the backend, less those
    the persistent cache served (JAX times both as a backend compile)."""

    def __init__(self):
        import jax

        self.requests = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._timed)
        jax.monitoring.register_event_listener(self._event)

    @property
    def n(self) -> int:
        return self.requests - self.hits

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._timed)
        jax.monitoring.unregister_event_listener(self._event)

    def _timed(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.requests += 1

    def _event(self, event, **kw):
        if event == CACHE_HIT_EVENT:
            self.hits += 1


@contextlib.contextmanager
def _nothing(name):
    yield


def annotator(enabled: bool):
    """Host spans on the profiler's clock around the harness's own calls."""
    if not enabled:
        return _nothing
    import jax

    return jax.profiler.TraceAnnotation


@dataclasses.dataclass
class Snapshot:
    t: float
    step_idx: int
    mixed_steps: int
    prefix_hit_tokens: int
    batch_sum: float
    batch_count: int
    preemptions: int
    prefill_tokens: int


def snapshot(engine) -> Snapshot:
    h = engine.registry.get("step.batch_size")
    return Snapshot(time.perf_counter(), engine.step_idx,
                    engine.stats["mixed_steps"],
                    engine.stats["prefix_hit_tokens"],
                    h.sum if h else 0.0, h.count if h else 0,
                    engine.stats["preemptions"],
                    engine.stats["prefill_tokens"])


@dataclasses.dataclass
class WindowResult:
    records: list
    start: float               # window start (host clock)
    end: float
    at_start: Snapshot
    at_end: Snapshot
    compiles: int
    lateness_s: list           # add time - due time, requests added
    profile: Optional[dict] = None   # traced stretch: bounds and snapshots


def drive(engine, requests, t0: float, warmup: dict, seconds: float,
          compiles: CompileCounter, profiler=None,
          profile_s: float = 0.0) -> WindowResult:
    """Run ``requests`` (due times relative to ``t0``) through ``engine``
    until the window closes.  ``warmup`` holds the mix's ``warmup_s`` or
    ``warmup_steps``.  ``profiler`` (start, stop) callables trace the last
    ``profile_s`` seconds of the window."""
    from repro.serving import PoolOOM, SamplingParams

    note = annotator(profiler is not None)
    step0 = engine.step_idx
    start = end = prof_at = None

    def open_window(at: float):
        nonlocal start, end, prof_at
        start, end = at, at + seconds
        prof_at = end - profile_s if profiler is not None else None

    if "warmup_steps" not in warmup:
        open_window(t0 + warmup["warmup_s"])
    records = [Record(due=t0 + r.due_s) for r in requests]
    lateness = []
    i, n = 0, len(requests)
    at_start = n_compiles = profile = None

    def on_token(rec):
        def cb(req, tok):
            rec.tokens.append(tok)
            rec.times.append(time.perf_counter())
        return cb

    while True:
        now = time.perf_counter()
        if start is None and engine.step_idx - step0 >= warmup["warmup_steps"]:
            open_window(now)
        if start is not None and at_start is None and now >= start:
            at_start = snapshot(engine)
            n_compiles = compiles.n
        if prof_at is not None and profile is None and now >= prof_at:
            profiler[0]()
            profile = {"begin": snapshot(engine)}
        if end is not None and now >= end:
            break
        with note("add_request"):
            while i < n and records[i].due <= now:
                rec = records[i]
                rec.added = time.perf_counter()
                lateness.append(rec.added - rec.due)
                r = requests[i]
                try:
                    rec.req = engine.add_request(
                        r.prompt, SamplingParams(max_new_tokens=r.max_new_tokens),
                        on_token=on_token(rec))
                except PoolOOM as e:
                    rec.error = f"PoolOOM: {e}"
                i += 1
        if engine.has_work():
            with note("engine.step"):
                engine.step()
        else:
            wake = [records[i].due if i < n else end, end]
            if prof_at is not None and profile is None:
                wake.append(prof_at)
            wake = [t for t in wake if t is not None] or [now]
            with note("wait_due"):
                time.sleep(max(0.0, min(wake) - now))
    at_end = snapshot(engine)
    if profile is not None:
        profile["end"] = at_end
        profiler[1]()
    return WindowResult(records=records[:i], start=start, end=end,
                        at_start=at_start, at_end=at_end,
                        compiles=compiles.n - n_compiles, lateness_s=lateness,
                        profile=profile)


# ---------------------------------------------------------------------------
# end-to-end statistics: all requests and all time of the window
# ---------------------------------------------------------------------------


def percentile(values, q: float) -> Optional[float]:
    """Linear-interpolated percentile; None for no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttft_samples(w: WindowResult) -> list[float]:
    """TTFT (s) of every request due in the window, counted from its due
    time; a request with no first token by the close counts end - due."""
    out = []
    for rec in w.records:
        if not (w.start <= rec.due < w.end):
            continue
        first = rec.times[0] if rec.times else None
        out.append((first if first is not None and first <= w.end
                    else w.end) - rec.due)
    return out


def queue_trend(w: WindowResult) -> dict:
    """For the knee sweep: requests due in the window still without a
    first token at its close, and TTFT p90 (s) of the requests due in its
    first and second half (a growing queue shows as a rising second)."""
    mid = (w.start + w.end) / 2
    halves = ([], [])
    waiting = 0
    for rec in w.records:
        if not (w.start <= rec.due < w.end):
            continue
        done = rec.times and rec.times[0] <= w.end
        waiting += not done
        t = (rec.times[0] if done else w.end) - rec.due
        halves[rec.due >= mid].append(t)
    return {"waiting_at_close": waiting,
            "ttft_p90_first_half_s": percentile(halves[0], 90),
            "ttft_p90_second_half_s": percentile(halves[1], 90)}


def itl_samples(w: WindowResult) -> list[float]:
    """Every gap (s) between consecutive output tokens that ends inside
    the window."""
    out = []
    for rec in w.records:
        t = rec.times
        for a, b in zip(t, t[1:]):
            if w.start <= b < w.end:
                out.append(b - a)
    return out


def tokens_in_window(w: WindowResult) -> int:
    return sum(1 for rec in w.records for t in rec.times
               if w.start <= t < w.end)


def counts(w: WindowResult) -> tuple[int, int]:
    """(attempted, failed): requests in flight or due during the window;
    failed = refused at intake or finished as aborted, timed out or shed."""
    attempted = failed = 0
    for rec in w.records:
        if rec.times and rec.times[-1] < w.start and _finished(rec):
            continue   # done before the window opened
        attempted += 1
        if rec.error or (rec.req is not None and _aborted(rec.req)):
            failed += 1
    return attempted, failed


def _finished(rec) -> bool:
    from repro.serving.request import RequestState

    return rec.req is not None and rec.req.state is RequestState.FINISHED


def _aborted(req) -> bool:
    from repro.serving.request import FinishReason

    return req.finish_reason in (FinishReason.ABORTED, FinishReason.TIMEOUT,
                                 FinishReason.SHED)
