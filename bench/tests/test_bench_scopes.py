"""Device time by the program's scopes and idle gaps by its phases: on a
hand-built trace whose answers are worked out below, on the engine step
recorded before the program had scopes, and on steps recorded on a TPU v5e
with them."""

import gzip
import json
from pathlib import Path

import pytest

from bench import scopes as SC
from bench import trace as TR
from bench.families.decoder import SCOPES

DATA = Path(__file__).resolve().parent / "data"


def test_scope_of_op_names():
    step = "jit(_mixed_step_jit)/while/body/closed_call/"
    of = lambda name: SC.scope_of(name, SCOPES)  # noqa: E731
    assert of(step + "attention/linear/kqp,...kp->...kq/dot_general") \
        == "linear"
    assert of(step + "attention/gather") == "attention"
    assert of("jit(_mixed_step_jit)/sample/cond/reduce") == "sample"
    assert of(step + "attention/gather:") == "attention"
    assert of(step + "sub:") == SC.NO_SCOPE
    assert of("jit(_mixed_step_jit)/jit(take_along_axis)/gather") \
        == SC.NO_SCOPE
    assert of("") == SC.NO_SCOPE
    # a scope is a whole path component, never a part of one
    assert of("jit(f)/linear_ish/attention_mask") == SC.NO_SCOPE
    # the names are the family's: other names read the same path otherwise
    assert SC.scope_of(step + "attention/linear/dot_general",
                       ("attention", "experts")) == "attention"
    assert SC.scope_of(step + "experts/linear/dot_general",
                       ("attention", "experts")) == "experts"


def hand_trace():
    """bench/tests/test_bench_trace.py's hand trace with scoped operations
    and the engine's spans: a step 12-92 holding dispatch 14-30 and
    harvest 62-91 (its sync 62-72); a compile 0-11 inside add_request."""
    host = [("add_request", 0, 12), ("engine.step", 12, 80),
            ("wait_due", 92, 8)]
    program = [("step", 12, 80), ("dispatch", 14, 16), ("harvest", 62, 29),
               ("sync", 62, 10), ("plan", 200, 5),
               ("XlaCompile", 0, 11)]
    ops = [("while.1 while @-", 10, 50), ("fusion.2 fusion @linear", 10, 20),
           ("copy.3 copy @attention", 40, 10),
           ("fusion.2 fusion @linear", 70, 10),
           ("fusion.4 fusion @unembed", 95, 25)]
    return {"host": host, "program": program, "devices": [ops]}


def test_hand_built_trace():
    ev = hand_trace()
    r = SC.reduce(ev)
    base = TR.reduce(ev)
    # the harness's spans alone bound the stretch
    assert r["window_s"] == pytest.approx(100e-9) == base["window_s"]
    assert r["busy_s"] == pytest.approx(65e-9) == base["busy_s"]
    assert r["device_ops"] == base["device_ops"]
    assert r["scope_s"] == {"linear": pytest.approx(30e-9),
                            "-": pytest.approx(20e-9),
                            "attention": pytest.approx(10e-9),
                            "unembed": pytest.approx(5e-9)}
    assert sum(r["scope_s"].values()) == pytest.approx(r["busy_s"])
    # gaps 0-10 (a compile), 60-70 (harvest's sync), 80-95 (harvest)
    assert r["idle_gaps"] == [["harvest", pytest.approx(15e-9)],
                              ["XlaCompile", pytest.approx(10e-9)],
                              ["sync", pytest.approx(10e-9)]]


def test_no_device_ops_reads_nothing():
    ev = hand_trace()
    assert SC.reduce(dict(ev, devices=[])) is None
    assert SC.reduce(dict(ev, host=[])) is None


def test_step_recorded_before_scopes_reads_as_before():
    """The engine step recorded on the v5e before the program had scopes
    or profiler spans: the stretch, the busy time and every operation's
    self time read as ``bench.trace`` reads them, under no scope."""
    ev = json.loads((DATA / "v5e_step_profile.json").read_text())
    base = TR.reduce(ev)
    scoped = dict(ev, devices=[[(f"{n} @-", s, d) for n, s, d in ops]
                               for ops in ev["devices"]])
    r = SC.reduce(scoped)
    assert r["window_s"] == base["window_s"]
    assert r["busy_s"] == base["busy_s"]
    assert [[n.rpartition(" @")[0], v] for n, v in r["device_ops"]] \
        == base["device_ops"]
    assert r["idle_gaps"] == base["idle_gaps"]
    assert list(r["scope_s"]) == ["-"]


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 7 offset_ps: 5000 duration_ps: 3000 }
    events { metadata_id: 8 offset_ps: 9000 duration_ps: 1000 }
    events { metadata_id: 9 offset_ps: 11000 duration_ps: 2000 } }
  event_metadata { key: 7 value { id: 7
    name: "%fusion.1 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop"
    stats { metadata_id: 2 str_value: "jit(f)/attention/linear/dot_general" }
    stats { metadata_id: 3 uint64_value: 42 } } }
  event_metadata { key: 8 value { id: 8
    name: "%copy.2 = f32[2]{0} copy(f32[2]{0} %fusion.1)"
    stats { metadata_id: 2 ref_value: 4 } } }
  event_metadata { key: 9 value { id: 9
    name: "%while.3 = (f32[2]{0}) while((f32[2]{0}) %t)" } }
  stat_metadata { key: 2 value { id: 2 name: "tf_op" } }
  stat_metadata { key: 3 value { id: 3 name: "flops" } }
  stat_metadata { key: 4 value { id: 4 name: "jit(f)/sample/reduce" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000 }
    events { metadata_id: 2 offset_ps: 1000 duration_ps: 15000 }
    events { metadata_id: 3 offset_ps: 2000 duration_ps: 1000 }
    events { metadata_id: 4 offset_ps: 3000 duration_ps: 500 } }
  event_metadata { key: 1 value { id: 1 name: "engine.step" } }
  event_metadata { key: 2 value { id: 2 name: "step" } }
  event_metadata { key: 3 value { id: 3 name: "dispatch" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(f)" } }
}
"""


def test_scopes_read_from_event_metadata(tmp_path):
    """The op_name stat sits in each operation's event metadata (as a
    string or interned), where ``ProfileData`` does not show it."""
    from jax.profiler import ProfileData

    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "h.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    ev = SC.load(str(tmp_path), SCOPES)
    assert ev["devices"] == [[("fusion.1 fusion @linear", 1005.0, 3.0),
                              ("copy.2 copy @sample", 1009.0, 1.0),
                              ("while.3 while @-", 1011.0, 2.0)]]
    assert ev["host"] == [("engine.step", 1000.0, 20.0)]
    assert ev["program"] == [("step", 1001.0, 15.0), ("dispatch", 1002.0, 1.0)]


def recorded_steps() -> dict:
    """Engine steps recorded on the v5e with the program's scopes and
    spans, expanded from the fixture's names table."""
    fx = json.loads((DATA / "v5e_scoped_steps.json").read_text())
    names = fx["names"]
    return {"host": [tuple(h) for h in fx["host"]],
            "program": [tuple(p) for p in fx["program"]],
            "devices": [[(names[i], s, d) for i, s, d in ops]
                        for ops in fx["devices"]]}


def test_recorded_v5e_steps_by_scope():
    """Scope self times add up to the busy time; the scopes of the main
    path all took time; the harness's numbers are those of
    ``bench.trace``; every idle gap is named by a harness or program
    span."""
    ev = recorded_steps()
    r = SC.reduce(ev)
    unscoped = dict(ev, devices=[[(n.rpartition(" @")[0], s, d)
                                  for n, s, d in ops]
                                 for ops in ev["devices"]])
    base = TR.reduce(unscoped)
    assert r["window_s"] == base["window_s"]
    assert r["busy_s"] == base["busy_s"]
    assert [n.rpartition(" @")[0] for n, _ in r["device_ops"]] \
        == [n for n, _ in base["device_ops"]]
    assert all(" @" in n for n, _ in r["device_ops"])
    assert sum(r["scope_s"].values()) == pytest.approx(r["busy_s"], rel=0.01)
    assert {"linear", "attention", "unembed", "norm"} <= set(r["scope_s"])
    assert set(r["scope_s"]) <= set(SCOPES) | {SC.NO_SCOPE}
    spans = set(TR.HOST_SPANS) | set(SC.PROGRAM_SPANS)
    assert r["idle_gaps"] and {n for n, _ in r["idle_gaps"]} <= spans
    assert {n for n, _, _ in ev["program"]} <= set(SC.PROGRAM_SPANS)


def test_recorded_v5e_steps_through_the_wired_path():
    """The recorded steps read as a ``--trace 1`` run reads them
    (``bench.run``: ``scopes.reduce`` under the cell's family, then
    ``traced_context``): scope times and each step's parts filled, the
    three rooflines in (0, 105] %, and the device numbers those of
    ``bench.trace``.  The fixture holds no dispatch log; its two steps'
    rows stand in for those of a padded step of the time: 30 decoding
    slots at about 850 positions, one 64-token prefill chunk and its
    4-token tail."""
    import types

    from bench import model
    from bench import run as R
    from bench.work import peaks

    layout = R.Layout(Path(__file__).resolve().parents[2])
    cell = "codeqwen7b.chat_backlog"
    files = layout.cell(cell)
    family = files["family"]
    cfg = model.model_config(files["config"])
    ev = recorded_steps()
    summary = SC.reduce(ev)
    req = lambda i, n: types.SimpleNamespace(  # noqa: E731
        req_id=i, prompt=list(range(n)), num_cached_tokens=0,
        t_admitted=-1.0)
    reqs = {i: req(i, 800 + 3 * i) for i in range(30)}
    reqs.update({i: req(i, 68) for i in (30, 31)})
    log = [(1, i, "prefill", 800 + 3 * i) for i in range(30)]
    log += [(9, 31, "prefill", 64)]
    for step in (11, 12):
        log += [(step, i, "decode", 1) for i in range(30)]
    log += [(11, 30, "prefill", 64), (12, 31, "prefill", 4)]
    snap = lambda step, mixed: types.SimpleNamespace(  # noqa: E731
        step_idx=step, mixed_steps=mixed, prefix_hit_tokens=0,
        batch_sum=64.0 * step, batch_count=2 * step)
    steps = [p for p in ev["program"] if p[0] == "step"]
    engine = types.SimpleNamespace(
        dispatch_log=log,
        tracer=types.SimpleNamespace(events=[
            {"ph": "X", "name": "step", "dur": d / 1e3,
             "args": {"step": 11 + i}} for i, (_, _, d) in enumerate(steps)]))
    w = types.SimpleNamespace(
        records=[types.SimpleNamespace(req=r) for r in reqs.values()],
        start=0.0, end=1.0, at_start=snap(10, 10), at_end=snap(12, 12),
        profile={"begin": snap(10, 10), "end": snap(12, 12)})
    ctx = R.traced_context(engine, w, family, cfg, peaks("TPU v5 lite"),
                           summary, 32)
    assert ctx.scope_s == summary["scope_s"]
    assert {"linear", "attention", "unembed"} <= set(ctx.scope_s)
    assert len(ctx.stretch_parts) == ctx.stretch_steps == 2
    assert all(set(p) == {"linear", "attention", "unembed"}
               for p in ctx.stretch_parts)
    read = {m["name"]: layout.reader(m["name"])(ctx)
            for m in layout.metrics(cell, "per_layer")}
    for part in ("linear", "attention", "unembed"):
        assert 0 < read[f"{part}_roofline.backlog"] <= 105
    unscoped = dict(ev, devices=[[(n.rpartition(" @")[0], s, d)
                                  for n, s, d in ops]
                                 for ops in ev["devices"]])
    base = TR.reduce(unscoped)
    assert read["step_device_ms.backlog"] == 1e3 * base["busy_s"] / 2
    assert read["device_idle_share.backlog"] \
        == 100.0 * (1.0 - base["busy_s"] / base["window_s"])
    assert 0 < read["step_mfu.backlog"] <= 105
    assert read["host_ms_per_step.backlog"] > 0
