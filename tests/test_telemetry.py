"""Tests for the serving telemetry stack: the dependency-free metrics
registry (counters / gauges / fixed-bucket histograms), the dict-compatible
``EngineStats`` view, Chrome trace-event tracing (span coverage + schema
validation), cost-model calibration, per-request lifecycle timestamps
(harvest-time stamping, TTFT monotonicity), pool high-water marks, the
``CostModel`` protocol conformance of both bundled cost models, and
end-to-end stats consistency through a preempting prefix-sharing run."""

import collections
import json
import math

import numpy as np
import pytest

import jax

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serving import (CIMCostModel, ContinuousBatchingEngine,
                           CostModel, HBMCostModel, PagedKVPool,
                           SamplingParams)
from repro.serving.metrics import (Calibration, Counter, EngineStats, Gauge,
                                   Histogram, MetricsRegistry, render_report)
from repro.serving.tracing import (NULL_TRACER, ChromeTracer, NullTracer,
                                   load_trace, validate_trace)

CFG = ModelConfig(name="t", d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
                  d_ff=128, vocab=256, dtype="float32")


@pytest.fixture(scope="module")
def params():
    return T.init_params(jax.random.PRNGKey(0), CFG)


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------


def test_counter_monotone_and_reset():
    c = Counter("toks")
    c.inc()
    c.inc(4)
    assert c.value == 5 and isinstance(c.value, int)
    c.inc(0.5)           # float promotion (sim_latency_ns style)
    assert c.value == 5.5
    c.reset()
    assert c.value == 0


def test_gauge_tracks_excursion():
    g = Gauge("free")
    assert g.snapshot()["last"] is None
    for v in (5, 1, 9, 3):
        g.set(v)
    s = g.snapshot()
    assert s["last"] == 3 and s["min"] == 1 and s["max"] == 9
    assert s["mean"] == pytest.approx(4.5) and s["n"] == 4


def test_histogram_buckets_percentiles_overflow():
    h = Histogram("lat", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 1.5, 3.0, 100.0):   # 100 -> overflow bucket
        h.observe(v)
    assert h.count == 5 and h.sum == pytest.approx(106.5)
    snap = h.snapshot()
    assert snap["buckets"] == {"1": 1, "2": 2, "4": 1, "+Inf": 1}
    # p50 lands in the (1, 2] bucket; overflow percentiles clamp to the
    # last finite bound rather than inventing an upper edge
    assert 1.0 <= h.percentile(50) <= 2.0
    assert h.percentile(99) == 4.0
    h.reset()
    assert h.count == 0 and math.isnan(h.percentile(50))


def test_histogram_upper_bound_inclusive():
    h = Histogram("le", buckets=(1.0, 2.0))
    h.observe(1.0)       # le semantics: lands in the first bucket
    assert h.snapshot()["buckets"] == {"1": 1, "2": 0, "+Inf": 0}


def test_registry_get_or_create_and_kind_clash():
    reg = MetricsRegistry()
    assert reg.counter("a") is reg.counter("a")
    reg.gauge("g")
    with pytest.raises(TypeError, match="already registered"):
        reg.histogram("g")
    assert reg.get("missing") is None
    assert len(reg) == 2


def test_registry_snapshot_is_json_ready_and_reset_keeps_handles():
    reg = MetricsRegistry()
    c = reg.counter("c")
    h = reg.histogram("h", buckets=(1.0,))
    c.inc(3)
    h.observe(0.5)
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["counters"]["c"] == 3
    assert snap["histograms"]["h"]["count"] == 1
    reg.reset()
    assert c.value == 0 and h.count == 0
    c.inc()              # the old handle still feeds the registry
    assert reg.snapshot()["counters"]["c"] == 1


def test_engine_stats_dict_compat():
    reg = MetricsRegistry()
    s = EngineStats(reg)
    s["tokens_out"] += 3                       # augmented assignment
    s["prefix_hit_tokens"] = 17                # mirror-style assignment
    s["custom_key"] = 2                        # unknown keys auto-create
    assert s["tokens_out"] == 3 and s.tokens_out == 3
    assert s["prefix_hit_tokens"] == 17
    assert dict(s)["custom_key"] == 2
    assert reg.snapshot()["counters"]["engine.tokens_out"] == 3
    assert reg.snapshot()["counters"]["engine.custom_key"] == 2
    assert set(EngineStats(MetricsRegistry())) >= {
        "mixed_steps", "decode_tokens", "prefill_tokens", "tokens_out",
        "preemptions", "sim_latency_ns"}


def test_render_report_smoke():
    reg = MetricsRegistry()
    reg.counter("engine.tokens_out").inc(5)
    reg.gauge("pool.free_pages").set(3)
    reg.histogram("request.ttft_ms", buckets=(1.0, 10.0)).observe(2.0)
    cal = Calibration("s")
    cal.record(100.0, 200.0)
    text = render_report(reg, [cal])
    assert "engine.tokens_out" in text and "pool.free_pages" in text
    assert "request.ttft_ms" in text and "calibration[s]" in text


# ---------------------------------------------------------------------------
# calibration
# ---------------------------------------------------------------------------


def test_calibration_recovers_proportional_scale():
    cal = Calibration("step")
    for p in (10.0, 20.0, 40.0):
        cal.record(p, 3.0 * p)
    assert cal.scale == pytest.approx(3.0)
    assert cal.residuals() == pytest.approx([1.0, 1.0, 1.0])
    rep = cal.report()
    assert rep["n"] == 3 and rep["scale"] == pytest.approx(3.0)
    assert rep["residual_p50"] == pytest.approx(1.0)
    assert rep["residual_max"] == pytest.approx(1.0)


def test_calibration_guards_and_empty_report():
    cal = Calibration("step")
    cal.record(0.0, 5.0)     # nothing predicted: not a data point
    cal.record(5.0, -1.0)
    assert cal.n == 0
    rep = cal.report()
    assert rep["n"] == 0 and math.isnan(rep["scale"])


def test_calibration_feeds_registry_histogram():
    reg = MetricsRegistry()
    cal = Calibration("step", reg)
    cal.record(10.0, 20.0)
    h = reg.get("calibration.step.ratio")
    assert h is not None and h.count == 1
    assert h.sum == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------


def test_tracer_spans_nest_and_validate(tmp_path):
    tr = ChromeTracer()
    with tr.span("step", step=1):
        with tr.span("plan", step=1):
            pass
    tr.instant("preempt", req_id=3)
    tr.counter("pool_pages", free=5, shared=2)
    n = validate_trace(tr.to_json())
    assert n == 5     # process_name M + 2 X + 1 i + 1 C
    assert tr.span_counts() == {"plan": 1, "step": 1}
    # the inner span closed first and both carry positive-or-zero ts/dur
    xs = [e for e in tr.events if e["ph"] == "X"]
    assert [e["name"] for e in xs] == ["plan", "step"]
    assert xs[1]["dur"] >= xs[0]["dur"]
    path = tmp_path / "trace.json"
    tr.save(str(path))
    events = load_trace(str(path))
    assert len(events) == 5


def test_validate_trace_rejects_malformed():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_trace({"events": []})
    with pytest.raises(ValueError, match="invalid phase"):
        validate_trace([{"ph": "Z", "name": "x", "pid": 0, "tid": 0,
                         "ts": 0}])
    with pytest.raises(ValueError, match="lacks a name"):
        validate_trace([{"ph": "X", "pid": 0, "tid": 0, "ts": 0, "dur": 1}])
    with pytest.raises(ValueError, match="invalid dur"):
        validate_trace([{"ph": "X", "name": "x", "pid": 0, "tid": 0,
                         "ts": 0}])


def test_null_tracer_is_inert():
    assert not NULL_TRACER.enabled
    sp = NULL_TRACER.span("anything", step=1)
    assert sp is NULL_TRACER.span("else")    # one shared no-op instance
    with sp:
        pass
    NULL_TRACER.instant("x")
    NULL_TRACER.counter("y", v=1)
    assert NULL_TRACER.span_counts() == {}
    assert NULL_TRACER.to_json()["traceEvents"] == []
    with pytest.raises(ValueError):
        NullTracer().save("/tmp/nope.json")


# ---------------------------------------------------------------------------
# pool high-water mark
# ---------------------------------------------------------------------------


def test_pool_high_water_mark_survives_free():
    pool = PagedKVPool(n_pages=9, page_size=4)
    pool.allocate(1, 16)     # 4 pages
    pool.allocate(2, 8)      # +2 = 6 live
    pool.free(1)
    pool.free(2)
    st = pool.stats()
    assert st.allocated_pages == 0
    assert st.peak_pages == 6
    assert st.peak_bytes == 6 * st.page_bytes
    assert st.cache_evictions == 0


# ---------------------------------------------------------------------------
# CostModel protocol conformance (satellite: prefill_nj signature drift)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("make", [
    lambda: HBMCostModel.from_model_config(CFG),
    lambda: CIMCostModel(CFG, strategy="sparse", seq_len=64),
], ids=["hbm", "cim"])
def test_cost_model_protocol_conformance(make):
    cm = make()
    assert isinstance(cm, CostModel)
    # every protocol method accepts the cached_tokens discount kwarg, and a
    # fully-cached chunk is never priced above an uncached one
    for meth in (cm.prefill_ns, cm.prefill_nj):
        full, cached = meth(32), meth(32, cached_tokens=32)
        assert cached <= full
        assert meth(32, cached_tokens=16) <= full
    assert cm.decode_step_ns(4, 64.0) > 0
    assert cm.decode_step_nj(4, 64.0) >= 0


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------


def _shared_prefix_prompts(n=4, prefix_len=16, tail=3):
    sys_p = list(np.asarray(jax.random.randint(
        jax.random.PRNGKey(40), (prefix_len,), 0, CFG.vocab)))
    return [np.asarray(sys_p + [(17 * i + j) % CFG.vocab
                                for j in range(tail + i % 2)], np.int32)
            for i in range(n)]


def _run_contended(params, **kw):
    """A prefix-sharing run over a deliberately starved pool: preemption,
    COW and resume all fire, making it the worst case for accounting."""
    eng = ContinuousBatchingEngine(
        CFG, params, max_slots=4, page_size=4, max_len=48, n_pages=11,
        chunk_size=8, **kw)
    reqs = []
    for p in _shared_prefix_prompts():
        reqs.append(eng.add_request(p, SamplingParams(max_new_tokens=6)))
        eng.step()
    eng.run()
    eng.pool_host.check_invariants()
    return eng, reqs


def test_stats_consistency_through_preemption_and_sharing(params):
    """After a full contended run, every counter reconciles: tokens out
    against the requests' outputs, decode/prefill tokens against the
    per-span dispatch log, and the per-step histograms against the step
    counter."""
    eng, reqs = _run_contended(params)
    assert eng.stats["preemptions"] > 0, "starved pool never preempted"
    assert eng.stats["prefix_hit_tokens"] > 0, "nothing was shared"

    assert eng.stats["tokens_out"] == sum(len(r.output_tokens) for r in reqs)
    dec = sum(n for _, _, kind, n in eng.dispatch_log if kind == "decode")
    pre = sum(n for _, _, kind, n in eng.dispatch_log if kind == "prefill")
    assert dec == eng.stats["decode_tokens"]
    assert pre == eng.stats["prefill_tokens"]
    # the log covers exactly the executed steps
    assert {s for s, _, _, _ in eng.dispatch_log} <= set(
        range(1, eng.step_idx + 1))

    hists = eng.registry.snapshot()["histograms"]
    assert hists["step.batch_size"]["count"] == eng.stats["mixed_steps"]
    assert hists["step.prefill_tokens"]["count"] == eng.stats["mixed_steps"]
    # one TTFT and one e2e observation per finished request; admissions
    # (incl. resumes after preemption) at least one queue-wait each
    assert hists["request.ttft_ms"]["count"] == len(reqs)
    assert hists["request.e2e_ms"]["count"] == len(reqs)
    assert hists["request.queue_wait_ms"]["count"] >= len(reqs)
    assert hists["request.itl_ms"]["count"] == \
        eng.stats["tokens_out"] - len(reqs)

    ps = eng.pool_host.stats()
    assert ps.peak_pages >= ps.allocated_pages
    assert ps.peak_pages <= ps.n_pages
    assert ps.peak_bytes == ps.peak_pages * ps.page_bytes


def test_request_lifecycle_events_and_derived_latencies(params):
    eng, reqs = _run_contended(params)
    victim = max(reqs, key=lambda r: r.num_preemptions)
    assert victim.num_preemptions > 0
    names = [e for e, _ in victim.events]
    assert names[0] == "arrived" and names[-1] == "finished"
    assert "preempted" in names and "resumed" in names
    ts = [t for _, t in victim.events]
    assert ts == sorted(ts), "event timestamps must be monotone"
    for r in reqs:
        assert r.ttft is not None and r.ttft > 0
        assert r.queue_wait is not None and r.queue_wait >= 0
        assert r.e2e_latency is not None and r.e2e_latency >= r.ttft
        assert r.t_first_token >= r.t_admitted >= r.t_arrival
        assert r.t_finished >= r.t_last_token >= r.t_first_token


def test_trace_covers_every_iteration(params):
    eng, _ = _run_contended(params, trace=True)
    counts = eng.tracer.span_counts()
    assert counts["step"] == eng.step_idx
    assert counts["plan"] >= eng.step_idx          # replans only add
    assert counts["dispatch"] == eng.stats["mixed_steps"]
    assert counts["harvest"] == eng.stats["mixed_steps"]
    assert counts["sync"] == counts["harvest"]
    assert counts["admit"] >= 1
    # preemption leaves instant markers on the timeline
    instants = [e for e in eng.tracer.events if e["ph"] == "i"]
    assert len(instants) == eng.stats["preemptions"]
    validate_trace(eng.tracer.to_json())


def test_trace_save_roundtrip_from_engine(params, tmp_path):
    path = tmp_path / "eng_trace.json"
    eng = ContinuousBatchingEngine(CFG, params, max_slots=2, page_size=4,
                                   max_len=32, trace=str(path))
    eng.add_request(np.arange(5) % CFG.vocab,
                    SamplingParams(max_new_tokens=3))
    eng.run()
    assert eng.tracer.save() == str(path)   # path captured from trace=
    events = load_trace(str(path))
    assert any(e["name"] == "step" for e in events)


def test_ttft_monotone_in_queue_position(params):
    """Satellite regression: with one slot, serialized admissions must see
    strictly increasing first-token times in queue order — a dispatch-time
    stamp (before the lagged harvest syncs) would break this by antedating
    a queued request's first token."""
    eng = ContinuousBatchingEngine(CFG, params, max_slots=1, page_size=4,
                                   max_len=32)
    prompts = [np.asarray([7 * i + j for j in range(6)], np.int32) % CFG.vocab
               for i in range(3)]
    reqs = [eng.add_request(p, SamplingParams(max_new_tokens=4))
            for p in prompts]
    eng.run()
    firsts = [r.t_first_token for r in reqs]
    assert all(t > 0 for t in firsts)
    assert firsts == sorted(firsts)
    assert len(set(firsts)) == len(firsts), "first tokens cannot tie"
    # arrivals were microseconds apart, service is serialized: TTFT grows
    # with queue position
    ttfts = [r.ttft for r in reqs]
    assert ttfts == sorted(ttfts)


def test_metrics_off_keeps_counters_drops_extras(params):
    eng, reqs = _run_contended(params, metrics=False)
    assert eng.stats["tokens_out"] == sum(len(r.output_tokens) for r in reqs)
    assert eng.stats["preemptions"] > 0
    assert eng.dispatch_log == []
    assert eng.calibration.n == 0
    hists = eng.registry.snapshot()["histograms"]
    assert hists == {}
    assert eng.tracer is NULL_TRACER
    # lifecycle stamps are cheap and always on
    assert all(r.ttft is not None for r in reqs)


def test_engine_calibration_records_with_cost_model(params):
    eng, _ = _run_contended(
        params, cost_model=HBMCostModel.from_model_config(CFG))
    assert eng.calibration.n == eng.stats["mixed_steps"]
    rep = eng.calibration.report()
    assert math.isfinite(rep["scale"]) and rep["scale"] > 0
    assert math.isfinite(rep["residual_max"])


def test_telemetry_does_not_change_outputs(params):
    """Greedy outputs are bit-identical with full telemetry on vs off."""
    def run(**kw):
        eng, reqs = _run_contended(params, **kw)
        return [r.output_tokens for r in reqs]

    assert run(metrics=True, trace=True) == run(metrics=False)


# ---------------------------------------------------------------------------
# the profiler's view: named scopes in the mixed step, spans on its clock
# ---------------------------------------------------------------------------

SCOPES = ("linear", "attention", "unembed", "embed", "norm", "sample")


def _op_scopes(hlo_text: str) -> list[tuple[str, str]]:
    """(opcode, innermost program scope or "-") of every instruction of a
    compiled module's text that carries an ``op_name``."""
    import re

    out = []
    for m in re.finditer(r"= \S+ ([\w\-]+)\(.*?op_name=\"([^\"]+)\"",
                         hlo_text):
        path = m.group(2).split("/")
        scope = next((p for p in reversed(path) if p in SCOPES), "-")
        out.append((m.group(1), scope))
    return out


def test_mixed_step_ops_carry_layer_scopes():
    """The compiled mixed step of a tiny Monarch model: every matmul is a
    projection, attention or the unembedding; the KV page write and the
    page gathers sit in attention; the greedy argmax in sample."""
    import jax.numpy as jnp

    from repro.core.linear import MonarchSpec
    from repro.serving import engine as E

    cfg = ModelConfig(name="t", d_model=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=128, vocab=256, dtype="float32",
                      monarch=MonarchSpec(enable=True, min_dim=16))
    p = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    pool = jax.eval_shape(lambda: T.init_paged_pool(cfg, 9, 4))
    B, S = 2, 4
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    args = (p, pool, i32(B, S), i32(B), jnp.zeros((B,), bool), i32(B),
            jnp.ones((B,), jnp.int32), i32(B, 8), i32(B),
            jnp.ones((B,), bool), jnp.zeros((B,), jnp.float32),
            jnp.zeros((B, 2), jnp.uint32))
    hlo = E._mixed_step_jit.lower(*args, cfg=cfg).compile().as_text()
    ops = _op_scopes(hlo)
    by_op: dict = {}
    for op, scope in ops:
        by_op.setdefault(op, set()).add(scope)
    assert by_op["dot"] == {"linear", "attention", "unembed"}
    assert by_op["scatter"] == {"attention"}
    assert {"attention", "embed"} <= by_op["gather"]
    assert "norm" in by_op["reduce"]
    # argmax lowers to a reduce of (values, indices) pairs
    import re

    argmax = re.findall(r"= \(f32\[\d+\]\{0\}, s32\[\d+\]\{0\}\) "
                        r"reduce\(.*op_name=\"([^\"]+)\"", hlo)
    assert argmax and all("/sample/" in name for name in argmax)


def _profiled_engine_spans(params, trace: bool, logdir) -> dict:
    """Names of the engine's spans on the host planes of a profiler trace
    taken around one short run."""
    from jax.profiler import ProfileData

    eng = ContinuousBatchingEngine(CFG, params, max_slots=2, page_size=4,
                                   max_len=32, trace=trace)
    eng.add_request(np.arange(6) % CFG.vocab,
                    SamplingParams(max_new_tokens=3))
    eng.run()                                       # compile outside
    eng.add_request(np.arange(5) % CFG.vocab,
                    SamplingParams(max_new_tokens=3))
    jax.profiler.start_trace(str(logdir))
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    (path,) = logdir.glob("**/*.xplane.pb")
    data = ProfileData.from_file(str(path))
    names = ("step", "plan", "admit", "dispatch", "sync", "harvest")
    found: dict = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    found.setdefault(e.name, []).append(
                        dict((k, str(v)) for k, v in e.stats))
    return found


@pytest.mark.parametrize("trace", [True, False])
def test_engine_spans_land_in_profiler_trace(params, tmp_path, trace):
    found = _profiled_engine_spans(params, trace, tmp_path)
    if not trace:
        assert found == {}
        return
    assert {"step", "plan", "dispatch", "sync", "harvest"} <= set(found)
    # the step span is a step annotation, numbered like the JSON event
    assert all("step_num" in s for s in found["step"])
    assert len(found["dispatch"]) == len(found["harvest"])


def test_dispatch_span_carries_step_composition(params):
    eng, reqs = _run_contended(params, trace=True)
    disp = [e["args"] for e in eng.tracer.events
            if e["ph"] == "X" and e["name"] == "dispatch"]
    assert len(disp) == eng.stats["mixed_steps"]
    for a in disp:
        assert a["bucket"] & (a["bucket"] - 1) == 0        # a power of two
        assert 0 < a["prefill_rows"] + a["decode_rows"] <= a["spans"]
        # the span group: the row bucket of the prefill rows, each row the
        # chunk bucket wide; a decode-only step has none and bucket 1
        if a["prefill_rows"]:
            assert a["rows"] == 1 << (a["prefill_rows"] - 1).bit_length()
            assert a["bucket"] == eng.span_bucket == 8
        else:
            assert a["rows"] == 0 and a["bucket"] == 1
        assert a["positions"] == eng.max_slots + a["rows"] * a["bucket"]
    assert sum(a["positions"] for a in disp) == eng.stats["step_positions"]
    assert sum(a["decode_rows"] for a in disp) == eng.stats["decode_tokens"]
    assert any(a["prefill_rows"] and a["decode_rows"] for a in disp)
    assert any(a["rows"] == 0 for a in disp)
    assert {a["rows"] for a in disp} > {0, 1}
    # the dispatch log lists each step's rows, and tracing does not move it
    per_step = collections.Counter(step for step, *_ in eng.dispatch_log)
    assert [per_step[a["step"]] for a in disp] == [
        a["prefill_rows"] + a["decode_rows"] for a in disp]
    quiet, quiet_reqs = _run_contended(params)

    def log(e, rs):   # request ids count up across engines: use the order
        order = {r.req_id: i for i, r in enumerate(rs)}
        return [(s, order[rid], kind, n) for s, rid, kind, n in e.dispatch_log]

    assert log(quiet, quiet_reqs) == log(eng, reqs)
    assert quiet.stats["step_positions"] == eng.stats["step_positions"]
