"""A step's count split into the layers the program scopes, and the
roofline share each layer's reader computes from it."""

import dataclasses
import types

import pytest

from bench import parts as P
from bench.work import Shape, shape_of, step_work

ROWS = [
    [(0, 4, True), (10, 1, True), (5, 2, False)],
    [(0, 64, False), (512, 64, True), (3071, 1, True)],
    [(7, 3, False)],
    [],
]


@pytest.fixture
def small():
    from repro.configs import get_config
    from repro.core.linear import MonarchSpec

    return dataclasses.replace(
        get_config("codeqwen1_5-7b"), d_model=16, n_layers=2, n_heads=2,
        n_kv_heads=1, head_dim=8, d_ff=32, vocab=50, dtype="bfloat16",
        monarch=MonarchSpec(enable=True, policy="paper", min_dim=16))


@pytest.mark.parametrize("rows", ROWS)
def test_parts_add_up_to_the_step(small, rows):
    s = shape_of(small)
    flops, nbytes = step_work(s, rows)
    parts = P.step_parts(s, rows)
    assert set(parts) == set(P.PARTS)
    assert sum(f for f, _ in parts.values()) == pytest.approx(flops, rel=1e-12)
    assert sum(b for _, b in parts.values()) <= nbytes


def test_parts_by_hand(small):
    s = shape_of(small)
    parts = P.step_parts(s, [(0, 4, True), (10, 1, True), (5, 2, False)])
    # tokens 7, keys 34, two sampling rows; weights of 1280 factor
    # parameters a layer in bf16; KV of 15 context + 7 new positions
    assert parts["linear"] == (2 * 1280 * 2 * 7, 2 * 1280 * 2)
    assert parts["attention"] == (4 * 2 * 8 * 34 * 2, (15 + 7) * 64)
    assert parts["unembed"] == (2 * 16 * 50 * 2, 16 * 50 * 2)


def test_roofline_share_of_a_scope():
    s = Shape(layer_params=10, layer_bytes=20, n_layers=1, d_model=4,
              vocab=8, heads=1, kv_heads=1, head_dim=2, dtype_bytes=2,
              final_norm_bytes=8)
    steps = [P.step_parts(s, [(0, 3, True)]), P.step_parts(s, [(3, 1, True)])]
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    ctx = types.SimpleNamespace(peaks=peaks, stretch_parts=steps,
                                scope_s={"linear": 4.0, "unembed": 0.0})
    # linear: F 60 then 20 over 100/s, B 20 twice over 10/s -> 2 + 2 s
    assert P.roofline_share(ctx, "linear") == pytest.approx(100.0)
    # a scope that took no time, or no scope times at all, reads nothing
    assert P.roofline_share(ctx, "unembed") is None
    assert P.roofline_share(ctx, "attention") is None
    bare = types.SimpleNamespace(peaks=peaks, stretch_work=[(1.0, 1.0)])
    assert P.roofline_share(bare, "linear") is None


@pytest.mark.parametrize("metric", ["linear_roofline", "attention_roofline",
                                    "unembed_roofline"])
def test_readers_find_nothing_without_scope_times(metric):
    from pathlib import Path

    from bench.run import Layout

    read = Layout(Path(__file__).resolve().parents[2]).reader(
        f"{metric}.backlog")
    bare = types.SimpleNamespace(
        trace={"window_s": 1.0, "busy_s": 1.0}, stretch_work=[(1.0, 1.0)],
        peaks={"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0})
    assert read(bare) is None
    steps = [{"linear": (2.0, 1.0), "attention": (4.0, 1.0),
              "unembed": (1.0, 8.0)}]
    ctx = types.SimpleNamespace(
        peaks={"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0},
        stretch_parts=steps,
        scope_s={"linear": 4.0, "attention": 8.0, "unembed": 16.0})
    assert read(ctx) == pytest.approx(50.0)
