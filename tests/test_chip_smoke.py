"""CPU guards for the one-chip serving path at reduced size.

* A bf16 model over fp32 weights keeps its residual stream in bf16 through
  every scanned layer: mixing the two used to promote the layer outputs to
  fp32, and the scan refused the carry before anything compiled.
* ``chip_smoke.py``'s phase function and sizing run end to end on a reduced
  config.  Here the backend is the CPU, so the Pallas kernels execute in
  interpret mode; the script itself only ever runs on a TPU.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.linear import linear_apply, linear_init, MonarchSpec
from repro.models import transformer as T

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke as CS  # noqa: E402


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(get_config("codeqwen1_5-7b").reduced(),
                               dtype="bfloat16")


@pytest.mark.parametrize("spec", [None, MonarchSpec(enable=True, min_dim=64)])
def test_linear_apply_keeps_activation_dtype(spec):
    p = linear_init(jax.random.PRNGKey(0), 128, 256, spec=spec, use_bias=True)
    x = jnp.ones((2, 3, 128), jnp.bfloat16)
    assert linear_apply(p, x).dtype == jnp.bfloat16


def test_bf16_stream_over_fp32_params_traces_mixed_step(cfg):
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    assert params["decoder"]["layers"]["ffn"]["w1"]["L"].dtype == jnp.float32
    B, S, pg, mp = 2, 8, 8, 4
    pool = T.init_paged_pool(cfg, 1 + B * mp, pg)
    logits, new_pool = jax.eval_shape(
        lambda p, pool: T.paged_mixed_step(
            p, jnp.zeros((B, S), jnp.int32), jnp.zeros((B,), jnp.int32),
            jnp.full((B,), S, jnp.int32),
            jnp.arange(1, 1 + B * mp, dtype=jnp.int32).reshape(B, mp),
            pool, cfg), params, pool)
    assert logits.shape == (B, cfg.vocab_padded)
    assert new_pool["layers"]["attn"]["k_pages"].dtype == jnp.bfloat16


def test_bf16_engine_over_fp32_params_serves(cfg):
    from repro.serving import ContinuousBatchingEngine, SamplingParams

    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = ContinuousBatchingEngine(cfg, params, max_slots=4, page_size=8,
                                   max_len=48, chunk_size=8)
    # served weights are stored in the activation dtype
    assert eng.params["embedding"]["table"].dtype == jnp.bfloat16
    reqs = [eng.add_request(np.arange(3 + 5 * i) % cfg.vocab,
                            SamplingParams(max_new_tokens=3))
            for i in range(3)]
    for _ in range(4):
        eng.step()
    eng.run()
    assert all(len(r.output_tokens) == 3 for r in reqs)
    eng.pool_host.check_invariants()


@pytest.mark.parametrize("phase", ["a", "b", "c"])
def test_chip_smoke_phase_reduced(cfg, phase):
    pcfg, kw = CS.phases(cfg)[phase]
    params = CS.make_params(cfg)
    prompts = CS.make_prompts(cfg.vocab, n=4, lengths=(8, 24))
    res = CS.run_phase(phase, pcfg, params, prompts, max_len=32,
                       n_pages=1 + CS.SLOTS * 2, chunk=16, engine_kw=kw,
                       new_tokens=4)
    assert [len(o) for o in res["outputs"]] == [4] * 4
    if kw.get("use_paged_kernel"):
        assert res["kernel_dispatches"] > 0 and res["dense_fallbacks"] == 0
    if kw.get("quantize"):
        base = CS.run_phase("a", cfg, params, prompts, max_len=32,
                            n_pages=1 + CS.SLOTS * 2, chunk=16, engine_kw={},
                            new_tokens=4)
        assert res["param_bytes"] < base["param_bytes"]


def test_chip_smoke_logits_agree_reduced(cfg):
    plan = CS.phases(cfg)
    params = CS.make_params(cfg)
    prompts = CS.make_prompts(cfg.vocab, n=CS.SLOTS, lengths=(16, 24))
    CS.check_logits(CS.reference_logits(cfg, params, prompts, span=16),
                    {"(a)": CS.step_logits(*plan["a"], params, prompts,
                                           span=16),
                     "(b)": CS.step_logits(*plan["b"], params, prompts,
                                           span=16)})


def test_chip_smoke_sizing_reduced(cfg):
    params = CS.make_params(cfg)
    prompts = CS.make_prompts(cfg.vocab, n=4, lengths=(5, 8))
    size = CS.size_run(cfg, params, {"a": CS.phases(cfg)["a"]}, prompts,
                       limit=2**40)
    assert size["chunk"] == 8 and size["max_len"] == 48
    assert size["n_pages"] == 1 + CS.SLOTS * 3
    spans = [s for s, _, _ in size["report"]["a"]["buckets"]]
    assert spans == [1, 2, 4, 8]
    # a budget below the smallest step leaves nothing to run
    with pytest.raises(RuntimeError, match="does not fit"):
        CS.size_run(cfg, params, {"a": CS.phases(cfg)["a"]}, prompts,
                    limit=1)
