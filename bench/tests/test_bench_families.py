"""A model family is found by the name in its configuration file, and a
configuration, its cell and a per-layer metric that reads a scope of its
own run through the harness with no edit to the harness: on the CPU, at
the tiny size of ``bench/tests/tiny.py``.  The ``decoder`` family is the
dense decoder of ``bench/model.py`` and ``bench/reference.py`` unchanged;
nested settings of a configuration reach the program's dataclasses."""

import dataclasses
import json

import jax
import numpy as np
import pytest

from bench import model
from bench import run as R
from bench import scopes as SC
from bench.tests import tiny

# The dense decoder's weights and reference, its projections read under a
# scope named ``proj`` and their FLOPs counted twice; every call recorded.
FAMILY = '''
from bench.families import decoder

SCOPES = ("proj", "attention", "unembed")
CALLS = {"weight_tree": 0, "score": 0, "work": [], "parts": []}
shape_of = decoder.shape_of


def weight_tree(key, cfg):
    CALLS["weight_tree"] += 1
    return decoder.weight_tree(key, cfg)


def score(model, weights, prompt, served, length):
    CALLS["score"] += 1
    return decoder.score(model, weights, prompt, served, length)


def step_work(shape, rows):
    flops, nbytes = decoder.step_work(shape, rows)
    CALLS["work"].append((2 * flops, nbytes))
    return CALLS["work"][-1]


def step_parts(shape, rows):
    parts = decoder.step_parts(shape, rows)
    flops, nbytes = parts.pop("linear")
    parts["proj"] = (2 * flops, nbytes)
    CALLS["parts"].append(parts)
    return parts
'''
READER = ("from bench.parts import roofline_share\n\n\n"
          "def read(ctx):\n    return roofline_share(ctx, 'proj')\n")
CELL = "twin.sessions"
# the op_name paths of a traced step, scoped by the names ``load`` is given
OPS = (("fusion.1 fusion", "jit(step)/while/body/proj/dot_general", 10, 40),
       ("fusion.2 fusion", "jit(step)/while/body/attention/gather", 50, 30),
       ("fusion.3 fusion", "jit(step)/unembed/dot_general", 80, 10))


def add_family_cell(root):
    """The files and entries a configuration of a new family brings: the
    family, the configuration naming it, the cell's limits, a metric
    reader of the family's own scope."""
    b = root / "bench"
    (b / "families" / "twin.py").write_text(FAMILY)
    (b / "metrics" / "proj_roofline.py").write_text(READER)
    conf = dict(tiny.CONFIG, name="twin", family="twin")
    (b / "configs" / "twin.json").write_text(json.dumps(conf))
    (b / "limits" / f"{CELL}.json").write_text(json.dumps(tiny.LIMITS))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "twin", "source": "test",
                            "file": "bench/configs/twin.json", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "twin",
                              "traffic": "tiny_sessions", "chips": 1,
                              "why": "test"})
    for name in ("proj_roofline.sessions", "step_mfu.sessions"):
        spec["per_layer"].append({
            "name": name, "unit": "%", "better": "higher",
            "source": "device_trace", "layer": "test",
            "moves": "output_tok_s", "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    root = tiny.write(tmp_path_factory.mktemp("tiny"))
    add_family_cell(root)
    return R.Layout(root)


def run_cell(layout, trace: int):
    args = R.parse(["--workload", CELL, "--seed", str(2**33 + 7),
                    "--seconds", "1.5", "--trace", str(trace)])
    return R.run(args, layout, chip=False)


def test_family_found_by_name(layout):
    twin = layout.cell(CELL)["family"]
    assert twin is layout.family("twin")
    assert twin.SCOPES == ("proj", "attention", "unembed")
    assert layout.cell("tiny.backlog")["family"] is layout.family("decoder")
    with pytest.raises(FileNotFoundError):
        layout.family("no_such_family")


def test_family_weights_and_reference_are_used(layout):
    twin = layout.family("twin")
    out = run_cell(layout, trace=0)
    assert out["correct"] is True
    assert twin.CALLS["weight_tree"] > 0
    assert twin.CALLS["score"] > 0
    assert out["check"]["sampled_tokens"]["value"] >= tiny.LIMITS[
        "min_tokens"]


def test_traced_run_reads_the_family_scopes_and_counts(layout, monkeypatch):
    """A traced run: the trace is read under the family's scope names, the
    stretch's parts are the family's, and the new metric reads its own
    scope's self time against its own count."""
    twin = layout.family("twin")
    given = []

    def load(logdir, scopes):
        given.append(scopes)
        ops = [(f"{name} @{SC.scope_of(path, scopes)}", s, d)
               for name, path, s, d in OPS]
        return {"host": [("engine.step", 0, 100)], "devices": [ops],
                "program": [("step", 0, 100)]}

    monkeypatch.setattr(SC, "load", load)
    del twin.CALLS["parts"][:], twin.CALLS["work"][:]
    out = run_cell(layout, trace=1)
    assert given == [twin.SCOPES]
    assert out["correct"] is True
    parts = twin.CALLS["parts"]
    assert parts and all("proj" in p and "linear" not in p for p in parts)
    need = sum(max(f, b) for f, b in (p["proj"] for p in parts))
    # peaks of 1 FLOP/s and 1 byte/s off the chip; the scope ran 40 ns
    assert out["metrics"]["proj_roofline.sessions"]["value"] \
        == pytest.approx(100.0 * need / 40e-9)
    # the whole step against the peaks, the family's count over 100 ns
    need = sum(max(f, b) for f, b in twin.CALLS["work"])
    assert out["metrics"]["step_mfu.sessions"]["value"] \
        == pytest.approx(100.0 * need / 100e-9)


def test_decoder_family_is_the_dense_decoder(tmp_path):
    """``decoder`` draws the weights of ``bench/model.make_weights`` and
    scores as ``bench/reference.score``, bit for bit."""
    from bench.reference import score

    layout = R.Layout(tiny.write(tmp_path))
    files = layout.cell("tiny.backlog")
    decoder = files["family"]
    cfg = model.model_config(files["config"])
    got = model.make_weights(cfg, 2**40 + 3, decoder.weight_tree)
    want = model.make_weights(cfg, 2**40 + 3)
    leaves = jax.tree_util.tree_leaves
    for a, b in zip(map(np.asarray, leaves(got)),
                    map(np.asarray, leaves(want))):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, 20, dtype=np.int32)
    served = rng.integers(0, cfg.vocab, 6, dtype=np.int32)
    m = files["config"]["model"]
    a = decoder.score(m, got, prompt, served, 64)
    b = score(m, want, prompt, served, 64)
    for k in ("best", "served", "argmax"):
        assert np.array_equal(a[k], b[k])


def test_nested_settings_reach_the_dataclasses():
    from repro.core.linear import MonarchSpec
    from repro.models.config import MoEConfig, SSMConfig

    spec = dict(tiny.CONFIG, model=dict(
        tiny.MODEL, attn_pattern=["local", "local", "local", "global"],
        moe={"n_experts": 8, "top_k": 2, "d_expert": 64},
        ssm={"d_state": 16, "a_init_range": [1.0, 4.0]}))
    cfg = model.model_config(spec)
    assert cfg.attn_pattern == ("local", "local", "local", "global")
    assert isinstance(cfg.moe, MoEConfig)
    assert cfg.moe == MoEConfig(n_experts=8, top_k=2, d_expert=64)
    assert isinstance(cfg.ssm, SSMConfig)
    assert cfg.ssm == SSMConfig(d_state=16, a_init_range=(1.0, 4.0))
    # a dataclass the registry entry already holds is replaced field by field
    assert isinstance(cfg.monarch, MonarchSpec)
    assert cfg.monarch.min_dim == 64
    base = model.model_config(dict(tiny.CONFIG, model={}))
    assert cfg.monarch == dataclasses.replace(base.monarch, enable=True,
                                              policy="paper", min_dim=64)
