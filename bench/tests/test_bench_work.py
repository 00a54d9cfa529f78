"""FLOP and byte counts of a step against hand-worked small shapes, and the
rebuild of each step's rows from the engine's dispatch log."""

import dataclasses
import types

import pytest

from bench.work import Shape, shape_of, step_work, steps_from_log


@pytest.fixture
def small():
    from repro.configs import get_config
    from repro.core.linear import MonarchSpec

    cfg = dataclasses.replace(
        get_config("codeqwen1_5-7b"), d_model=16, n_layers=2, n_heads=2,
        n_kv_heads=1, head_dim=8, d_ff=32, vocab=50, dtype="bfloat16",
        monarch=MonarchSpec(enable=True, policy="paper", min_dim=16))
    return cfg


def test_monarch_parameters_by_hand(small):
    # paper policy, blocks of about sqrt(d_in):
    # wq, wo 16->16: k=q=p=s=4 -> 64 + 64;  wk, wv 16->8 stay dense
    # (8 < min_dim): 128;  w1, wg 16->32: 64 + 128;
    # w2 32->16: k=8, q=8, p=4, s=2 -> 256 + 128
    s = shape_of(small)
    assert s.layer_params == 128 + 128 + 128 + 128 + 192 + 192 + 384 == 1280
    # the program's own count agrees (second witness)
    emb = small.vocab_padded * small.d_model * 2
    assert s.layer_params * small.n_layers == small.param_count() - emb
    assert s.layer_bytes == 2 * (1280 + 2 * 16)
    assert s.kv_bytes_per_token == 2 * 2 * 1 * 8 * 2


def test_step_work_by_hand(small):
    s = shape_of(small)
    rows = [(0, 4, True), (10, 1, True), (5, 2, False)]
    flops, nbytes = step_work(s, rows)
    # tokens 7; keys seen 0*4+10, 10+1, 2*5+3 = 34; two rows sample
    assert flops == 2 * 1280 * 2 * 7 + 4 * 2 * 8 * 34 * 2 + 2 * 16 * 50 * 2
    # weights 2 layers + final norm + unembedding + 7 embedding rows +
    # KV of 15 context positions read and 7 new rows written
    assert nbytes == 2 * 2624 + 32 + 1600 + 7 * 16 * 2 + (15 + 7) * 64


def test_no_sampling_row_reads_no_unembedding():
    s = Shape(layer_params=10, layer_bytes=20, n_layers=1, d_model=4,
              vocab=8, heads=1, kv_heads=1, head_dim=2, dtype_bytes=2,
              final_norm_bytes=8)
    f, b = step_work(s, [(0, 3, False)])
    assert f == 2 * 10 * 3 + 4 * 1 * 2 * 6
    assert b == 20 + 8 + 3 * 4 * 2 + 3 * s.kv_bytes_per_token


def test_rows_rebuilt_from_dispatch_log():
    req = lambda n, hit: types.SimpleNamespace(  # noqa: E731
        prompt=list(range(n)), num_cached_tokens=hit)
    reqs = {1: req(100, 0), 2: req(40, 32)}
    log = [(1, 1, "prefill", 64), (1, 2, "prefill", 8),
           (2, 1, "prefill", 36), (2, 2, "decode", 1),
           (3, 1, "decode", 1), (3, 2, "decode", 1),
           # request 2 preempted and resumed: prefill again from its hit
           (4, 2, "prefill", 9), (4, 1, "decode", 1)]
    rows = steps_from_log(log, reqs, 1, 4)
    assert rows == {2: [(64, 36, True), (40, 1, True)],
                    3: [(100, 1, True), (41, 1, True)],
                    4: [(32, 9, True), (101, 1, True)]}
