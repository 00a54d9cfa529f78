"""The plain reference against the engine at ``reduced()`` size on the CPU:
chunked prefill through the page pool, decoding that reads pages written in
earlier steps, and a request whose prefix pages another request wrote."""

import dataclasses

import numpy as np
import pytest

from bench import correct, model
from bench.reference import score


def reference_sizes(cfg) -> dict:
    keys = ("d_model", "n_heads", "n_kv_heads", "norm_type", "ffn_type",
            "rope_theta", "n_layers", "vocab", "tie_embeddings")
    return dict({k: getattr(cfg, k) for k in keys}, head_dim=cfg.hd)


def serve(cfg, weights, prompts, new_tokens):
    """Greedy tokens of each prompt through the engine's own entry; every
    request after the first shares the first one's prefix pages."""
    from repro.serving import ContinuousBatchingEngine, SamplingParams

    eng = ContinuousBatchingEngine(cfg, weights, max_slots=4, page_size=16,
                                   max_len=128, chunk_size=16)
    first = eng.add_request(prompts[0],
                            SamplingParams(max_new_tokens=new_tokens))
    eng.run()
    rest = [eng.add_request(p, SamplingParams(max_new_tokens=new_tokens))
            for p in prompts[1:]]
    eng.run()
    if rest:   # the second prompt matched the first's two full pages
        assert eng.stats["prefix_hit_tokens"] >= 32
    return [(p, r.output_tokens) for p, r in zip(prompts, [first, *rest])]


@pytest.fixture(params=["codeqwen1_5-7b", "nemotron-4-15b"])
def reduced(request):
    from repro.configs import get_config

    return get_config(request.param).reduced()


def prompts_for(cfg, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, cfg.vocab, 45, dtype=np.int32)
    b = np.concatenate([a, rng.integers(0, cfg.vocab, 11, dtype=np.int32)])
    return [a, b]


def test_reference_agrees_with_engine_fp32(reduced):
    """float32 weights and activations on both sides: the engine's greedy
    tokens are the reference's argmax, and no served token lies more than
    1e-4 below the reference's best logit (fp32 rounding of a 2-layer,
    d_model 128 stack is ~1e-6 of logits of order 1)."""
    weights = model.make_weights(reduced, 7)
    served = serve(reduced, weights, prompts_for(reduced), 12)
    r = correct.widest_gap(score, reference_sizes(reduced), weights, served,
                           128)
    assert r["tokens"] == 24
    assert r["argmax_agreement"] == 1.0
    assert r["max_logit_gap"] <= 1e-4


def test_reference_bounds_bf16_engine(reduced):
    """bf16 weights, activations and KV pages in the engine against the
    float32 reference: bf16 keeps 8 bits of mantissa, so logits of order 1
    move by ~1e-2; no served token lies more than 0.1 below the best."""
    cfg = dataclasses.replace(reduced, dtype="bfloat16")
    weights = model.make_weights(cfg, 8)
    served = serve(cfg, weights, prompts_for(cfg, 1), 12)
    r = correct.widest_gap(score, reference_sizes(cfg), weights, served, 128)
    assert r["max_logit_gap"] <= 0.1


def test_altered_token_is_caught(reduced):
    """A served token replaced by another: its gap is of the order of the
    logits' spread, far above the bf16 bound."""
    weights = model.make_weights(reduced, 9)
    (p, out), = serve(reduced, weights, prompts_for(reduced)[:1], 6)
    altered = out[:3] + [(out[3] + 1) % 500] + out[4:]
    r = correct.widest_gap(score, reference_sizes(reduced), weights,
                           [(p, altered)], 128)
    assert r["max_logit_gap"] > 0.1
