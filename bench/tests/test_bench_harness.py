"""The harness finds a cell's files by name, new ones included, prints the
result line in the agreed shape, and refuses to run without a TPU."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run as R
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
NEW_READER = "def read(ctx):\n    return 42.0\n"


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    root = tiny.write(tmp_path_factory.mktemp("tiny"),
                      extra_metrics={"tiny_counter": NEW_READER})
    return R.Layout(root)


def test_files_found_by_name(layout):
    files = layout.cell("tiny.sessions")
    assert files["config"]["name"] == "tiny"
    assert files["mix"]["name"] == "tiny_sessions"
    assert files["limits"] == tiny.LIMITS
    with pytest.raises(KeyError):
        layout.cell("no.such_cell")


def test_metric_added_as_a_file(layout):
    # a reader of its own, and a group metric served by its base's reader
    assert layout.reader("tiny_counter.backlog")(None) == 42.0
    read = layout.reader("batch_occupancy.backlog")
    ctx = R.TracedRun(trace=None, stretch_steps=0, stretch_work=[],
                      stretch_parts=[], scope_s={},
                      peaks={}, host_step_ms=[], prefix_hit_tokens=0,
                      prompt_tokens_admitted=0, batch_sum=6.0,
                      batch_count=2, max_slots=4)
    assert read(ctx) == 75.0
    with pytest.raises(FileNotFoundError):
        layout.reader("no_such_metric")


def test_metrics_of_a_cell(layout):
    e2e = [m["name"] for m in layout.metrics("tiny.backlog", "end_to_end")]
    assert e2e == ["itl_p95_ms", "output_tok_s", "setup_s"]
    e2e = [m["name"] for m in layout.metrics("tiny.sessions", "end_to_end")]
    assert e2e == ["ttft_p90_ms", "itl_p95_ms", "output_tok_s", "setup_s"]
    per = [m["name"] for m in layout.metrics("tiny.backlog", "per_layer")]
    assert per == ["host_ms_per_step.backlog", "batch_occupancy.backlog",
                   "prefix_hit_share.backlog", "tiny_counter.backlog"]


def test_benchmark_json_metrics_have_readers():
    """Every per-layer metric has a reader and moves a metric its cells
    report; a ``<part>_roofline`` reads a scope of each cell's family that
    the family's count splits out."""
    from bench import model

    layout = R.Layout(ROOT)
    for m in layout.spec["per_layer"]:
        assert callable(layout.reader(m["name"]))
        base = m["name"].split(".")[0]
        for cell in m["workloads"]:
            moves = {e["name"]: e for e in layout.spec["end_to_end"]}
            e = moves[m["moves"]]
            assert "workloads" not in e or cell in e["workloads"]
            if base.endswith("_roofline"):
                part = base[:-len("_roofline")]
                files = layout.cell(cell)
                family = files["family"]
                shape = family.shape_of(model.model_config(files["config"]))
                assert part in family.SCOPES
                assert part in family.step_parts(shape, [(0, 1, True)])
                assert m["unit"] == "%" and m["source"] == "device_trace"
    rooflines = {m["name"] for m in layout.metrics("codeqwen7b.chat_backlog",
                                                   "per_layer")
                 if "_roofline" in m["name"]}
    assert rooflines == {"linear_roofline.backlog",
                         "attention_roofline.backlog",
                         "unembed_roofline.backlog"}
    for w in layout.spec["workloads"]:
        files = layout.cell(w["name"])
        assert files["mix"]["max_len"] > 0
        assert set(files["limits"]) == {"compare", "sample_tokens",
                                        "min_tokens"}
        assert files["limits"]["compare"]


@pytest.mark.parametrize("trace", [0, 1])
def test_result_line_shape(layout, trace):
    args = R.parse(["--workload", "tiny.sessions", "--seed", "17",
                    "--seconds", "1.5", "--trace", str(trace)])
    out = R.run(args, layout, chip=False)
    line = json.loads(json.dumps(out))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "check"
    assert isinstance(line["correct"], bool)
    assert line["attempted"] >= line["failed"] >= 0
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    kind = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in layout.metrics("tiny.sessions", kind)}
    assert set(line["metrics"]) <= names
    assert line["metrics"], "a run reports at least one metric"
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    if not trace:
        assert set(line["metrics"]) == names
    for c in line["check"].values():
        assert "value" in c and ("max" in c or "min" in c)


def _run_bench(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "codeqwen7b.chat_backlog", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_refuses_without_a_tpu():
    p = _run_bench(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_bench(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def _layout_with(tmp_path, deployment: dict):
    """The tiny layout with the configuration's deployment replaced."""
    root = tiny.write(tmp_path)
    conf = dict(tiny.CONFIG, deployment=deployment)
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(conf))
    return R.Layout(root)


@pytest.mark.parametrize("key", ["no_such_setting", "max_len", "cfg"])
def test_deployment_key_refused_before_weights(tmp_path, monkeypatch, key):
    from bench import model

    def no_weights(*a, **k):
        raise AssertionError("weights made before the settings were read")

    monkeypatch.setattr(model, "make_weights", no_weights)
    layout = _layout_with(tmp_path, dict(tiny.CONFIG["deployment"],
                                         **{key: 1}))
    args = R.parse(["--workload", "tiny.backlog", "--seed", "5",
                    "--seconds", "1", "--trace", "0"])
    with pytest.raises(SystemExit, match=repr(key)):
        R.run(args, layout, chip=False)


def test_deployment_settings_reach_the_engine(tmp_path, capsys):
    """Every deployment key goes to the engine by name: a chunk size the
    harness never names reaches the scheduler, and the warm-up serves
    prompts up to it."""
    layout = _layout_with(tmp_path, dict(tiny.CONFIG["deployment"],
                                         chunk_size=32))
    args = R.parse(["--workload", "tiny.backlog", "--seed", "5",
                    "--seconds", "1", "--trace", "0"])
    out = R.run(args, layout, chip=False)
    assert out["correct"]
    assert "[warm] prompts of [1, 2, 4, 8, 16, 32] tokens" in \
        capsys.readouterr().err
    assert R.engine_settings({"chunk_size": 32}) == {"chunk_size": 32}


def test_warm_up_leaves_no_admission_to_compile():
    """After the harness's warm-up, admitting any number of requests at
    once, up to every slot, compiles nothing."""
    import jax
    import numpy as np

    from bench import model
    from bench.window import CompileCounter
    from repro.serving import ContinuousBatchingEngine, SamplingParams

    jax.clear_caches()
    cfg = model.model_config(tiny.CONFIG)
    engine = ContinuousBatchingEngine(cfg, model.make_weights(cfg, 7),
                                      max_len=64, **tiny.CONFIG["deployment"])
    rng = np.random.default_rng(7)
    R.warm(engine, R.span_buckets(engine.scheduler.cfg.chunk_size),
           cfg.vocab, False, rng)
    counter = CompileCounter()
    try:
        for k in range(1, engine.max_slots + 1):
            for i in range(k):
                engine.add_request(
                    rng.integers(0, cfg.vocab, 5 + 9 * i, dtype=np.int32),
                    SamplingParams(max_new_tokens=3 + 4 * i))
            engine.run()
    finally:
        counter.close()
    assert engine.stats["finished"] >= 10
    assert counter.n == 0
