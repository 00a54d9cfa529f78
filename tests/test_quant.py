"""Quantized decode fast path: per-block int8/int4 quantization, in-kernel
dequant exactness vs the dequantize-then-einsum oracles, exact QKV/gate-up
fusion, and end-to-end serving parity."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # optional dep

from repro.core import monarch as mn
from repro.core import quant as qn
from repro.core.linear import MonarchSpec, linear_apply, linear_init, linear_out_dim
from repro.kernels import ops
from repro.kernels.bdmm import bdmm, bdmm_q
from repro.kernels.monarch import fused_fits, monarch_fused, monarch_fused_q
from repro.kernels.ref import bdmm_q_ref, monarch_q_ref, monarch_ref
from repro.models import decode_path as DP
from repro.models import fuse as F
from repro.models import transformer as T
from repro.models.config import ModelConfig

CFG = ModelConfig(name="tq", d_model=128, n_layers=2, n_heads=4,
                  n_kv_heads=4, d_ff=256, vocab=512, dtype="float32",
                  monarch=MonarchSpec(enable=True, min_dim=64))


def _monarch_params(key=0, din=256, dout=512, k=16, q=16):
    dims = mn.MonarchDims(din=din, dout=dout, k=k, q=q)
    return mn.init_monarch(jax.random.PRNGKey(key), dims)


# fp32 summation order: a kernel contracts one diagonal block per 2-D dot,
# an oracle runs one batched einsum, and a fused projection one wider
# matmul.  On the CPU backend the orders differ by a few units in the last
# place of the output's scale (measured: <= 2e-6 at magnitudes ~5), so those
# comparisons allow ORACLE_ULPS of it.  Exactness stays asserted where the
# code guarantees it: in-VMEM dequant against the float kernel fed the
# dequantized factors.
ORACLE_ULPS = 16


def _assert_ulp_close(got, want, ulps=ORACLE_ULPS):
    want = np.asarray(want, np.float32)
    atol = ulps * np.finfo(np.float32).eps * float(np.abs(want).max())
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=atol)


# ---------------------------------------------------------------------------
# quantization: packing, error bounds
# ---------------------------------------------------------------------------


def test_pack_int4_roundtrip():
    v = jnp.clip(jax.random.randint(jax.random.PRNGKey(0), (3, 5, 8), -7, 8),
                 -7, 7).astype(jnp.int8)
    np.testing.assert_array_equal(qn.unpack_int4(qn.pack_int4(v)), v)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("shape", [(16, 16, 16), (3, 8, 32, 16), (1, 4, 4)])
def test_block_quant_error_bound(shape, bits):
    w = jax.random.normal(jax.random.PRNGKey(1), shape)
    stats = qn.quant_error_stats(w, bits)
    # per-block relative error is bounded by half a quantization step
    assert stats["max_block_rel_err"] <= stats["bound_block_rel"] + 1e-6


@given(
    k=st.integers(min_value=1, max_value=8),
    q=st.integers(min_value=1, max_value=8),
    logp=st.integers(min_value=1, max_value=5),
    bits=st.sampled_from([8, 4]),
    seed=st.integers(min_value=0, max_value=2**16),
)
@settings(deadline=None, max_examples=25)
def test_block_quant_error_bound_property(k, q, logp, bits, seed):
    w = jax.random.normal(jax.random.PRNGKey(seed), (k, q, 2 ** logp)) * (
        1.0 + seed % 7)
    stats = qn.quant_error_stats(w, bits)
    assert stats["max_block_rel_err"] <= stats["bound_block_rel"] + 1e-6
    # and the dequantized factor reconstructs within the same bound, scaled
    # by each block's absmax
    assert stats["max_abs_err"] <= (
        stats["bound_block_rel"] * float(jnp.max(jnp.abs(w))) + 1e-6)


def test_quantize_monarch_container_shapes():
    p = _monarch_params()
    for bits, last in ((8, 16), (4, 8)):
        qp = qn.quantize_monarch(p, bits)
        assert qp["Lq"].dtype == jnp.int8 and qp["Lq"].shape == (16, 16, last)
        assert qp["Ls"].shape == (16, 1, 1)
        assert qn.quant_bits(qp, 256) == bits
        assert qn.quantized_out_dim(qp) == 512


# ---------------------------------------------------------------------------
# kernels: in-VMEM dequant is EXACTLY the dequantize-then-multiply chain
# ---------------------------------------------------------------------------


def _dequantized(qp, k, p):
    deq = qn.dequantize_monarch(qp, k, p)
    return deq["L"], deq["R"]


@pytest.mark.parametrize("bits", [8, 4])
def test_bdmm_q_matches_oracle_exactly(bits):
    p = _monarch_params()
    qp = qn.quantize_monarch(p, bits)
    x = jax.random.normal(jax.random.PRNGKey(2), (40, 16, 16))
    got = bdmm_q(x, qp["Lq"], qp["Ls"], interpret=True)
    L, _ = _dequantized(qp, 16, 16)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(bdmm(x, L, interpret=True)))
    _assert_ulp_close(got, bdmm_q_ref(x, qp["Lq"], qp["Ls"]))


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("T_", [40, 128, 50])
def test_monarch_fused_q_matches_oracle_exactly(bits, T_):
    p = _monarch_params()
    qp = qn.quantize_monarch(p, bits)
    x = jax.random.normal(jax.random.PRNGKey(3), (T_, 256))
    got = monarch_fused_q(x, qp["Lq"], qp["Ls"], qp["Rq"], qp["Rs"],
                          interpret=True)
    L, R = _dequantized(qp, 16, 16)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(monarch_fused(x, L, R, interpret=True)))
    _assert_ulp_close(got, monarch_q_ref(x, qp["Lq"], qp["Ls"], qp["Rq"],
                                         qp["Rs"]))


@pytest.mark.parametrize("bits", [8, 4])
def test_ops_monarch_mm_q_and_linear_apply(bits):
    p = _monarch_params()
    qp = qn.quantize_monarch(p, bits)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 5, 256))
    want = monarch_q_ref(x.reshape(10, 256), qp["Lq"], qp["Ls"],
                         qp["Rq"], qp["Rs"])
    y_pallas = ops.monarch_mm_q(x, qp["Lq"], qp["Ls"], qp["Rq"], qp["Rs"])
    y_einsum = linear_apply(qp, x)
    assert y_pallas.shape == (2, 5, 512)
    L, R = _dequantized(qp, 16, 16)
    np.testing.assert_array_equal(np.asarray(y_pallas),
                                  np.asarray(ops.monarch_mm(x, L, R)))
    _assert_ulp_close(y_pallas.reshape(10, 512), want)
    np.testing.assert_array_equal(np.asarray(y_einsum.reshape(10, 512)),
                                  np.asarray(want))
    assert linear_out_dim(qp) == 512


def test_quantized_error_vs_fp32_small():
    p = _monarch_params()
    x = jax.random.normal(jax.random.PRNGKey(5), (16, 256))
    want = monarch_ref(x, p["L"], p["R"])
    for bits, tol in ((8, 0.05), (4, 0.5)):
        qp = qn.quantize_monarch(p, bits)
        got = linear_apply(qp, x)
        rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        assert rel < tol, (bits, rel)


def test_fused_fits_is_dtype_aware():
    # 4.2M factor params: 16.8 MB fp32 spills the 10 MB budget, 8.4 MB bf16
    # fits — the fit decision follows the STORED weight width
    big_l, big_r = (128, 128, 128), (128, 128, 128)
    assert not fused_fits(big_l, big_r, 4)          # fp32 spills ...
    assert fused_fits(big_l, big_r, 2)              # ... bf16 fits
    # the quantized fused kernel materializes fp32 dequant temporaries in
    # VMEM next to the stored int8 blocks: storage-only accounting would
    # admit this pair (4.2 MB), the honest working set (21 MB) must not
    assert fused_fits(big_l, big_r, 1)
    assert not fused_fits(big_l, big_r, 1, dequant_bytes=4)
    # a pair sized for the quantized budget passes with the temporaries
    sm_l, sm_r = (64, 64, 64), (64, 64, 64)
    assert fused_fits(sm_l, sm_r, 1, dequant_bytes=4)


def test_dispatch_table_caches():
    p = _monarch_params(key=7)
    x = jax.random.normal(jax.random.PRNGKey(8), (4, 256))
    ops.monarch_mm(x, p["L"], p["R"])
    before = ops.dispatch_cache_info().hits
    ops.monarch_mm(x, p["L"], p["R"])
    ops.monarch_mm(x, p["L"], p["R"])
    assert ops.dispatch_cache_info().hits >= before + 2


# ---------------------------------------------------------------------------
# fusion: exact (bitwise in fp32) QKV / gate-up concatenation
# ---------------------------------------------------------------------------


def test_fused_qkv_bitwise_matches_separate():
    from repro.models import layers as L

    attn = L.attention_init(jax.random.PRNGKey(0), CFG)
    fused = F.fuse_attention(attn)
    assert "wqkv" in fused
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, CFG.d_model))
    h, kv, hd = CFG.n_heads, CFG.n_kv_heads, CFG.hd
    qkv = linear_apply(fused["wqkv"], x)
    for name, lo, hi in (("wq", 0, h * hd),
                         ("wk", h * hd, (h + kv) * hd),
                         ("wv", (h + kv) * hd, (h + 2 * kv) * hd)):
        want = linear_apply(attn[name], x)
        np.testing.assert_array_equal(np.asarray(qkv[..., lo:hi]),
                                      np.asarray(want))


def test_fuse_model_decode_step_bitwise():
    params = T.init_params(jax.random.PRNGKey(0), CFG)
    fused = F.fuse_model(params)
    layer = fused["decoder"]["layers"]
    assert "wqkv" in layer["attn"] and "w1g" in layer["ffn"]
    tok = jnp.array([3, 5], dtype=jnp.int32)
    lo1, _ = T.decode_step(params, tok, T.init_decode_cache(CFG, 2, 16), CFG)
    lo2, _ = T.decode_step(fused, tok, T.init_decode_cache(CFG, 2, 16), CFG)
    _assert_ulp_close(lo2, lo1)


def test_fuse_model_gqa_fuses_kv_only():
    cfg = dataclasses.replace(CFG, n_kv_heads=2)
    params = T.init_params(jax.random.PRNGKey(1), cfg)
    fused = F.fuse_model(params)
    attn = fused["decoder"]["layers"]["attn"]
    assert "wkv" in attn and "wq" in attn and "wqkv" not in attn
    tok = jnp.array([7, 9], dtype=jnp.int32)
    lo1, _ = T.decode_step(params, tok, T.init_decode_cache(cfg, 2, 16), cfg)
    lo2, _ = T.decode_step(fused, tok, T.init_decode_cache(cfg, 2, 16), cfg)
    _assert_ulp_close(lo2, lo1)


def test_fuse_model_encdec_cross_attention_fuses_kv_only():
    cfg = dataclasses.replace(CFG, encdec=True, n_enc_layers=2)
    params = T.init_params(jax.random.PRNGKey(3), cfg)
    fused = F.fuse_model(params)
    xattn = fused["decoder"]["layers"]["xattn"]
    assert "wkv" in xattn and "wqkv" not in xattn     # q reads another stream
    assert "wqkv" in fused["decoder"]["layers"]["attn"]
    batch = {"tokens": jnp.zeros((2, 6), jnp.int32),
             "enc_tokens": jnp.zeros((2, 5), jnp.int32)}
    lo1, _ = T.forward(params, batch, cfg, train=False)
    lo2, _ = T.forward(fused, batch, cfg, train=False)
    _assert_ulp_close(lo2, lo1)


def test_fused_proj_init_and_forward():
    cfg = dataclasses.replace(CFG, fused_proj=True)
    params = T.init_params(jax.random.PRNGKey(2), cfg)
    layer = params["decoder"]["layers"]
    assert "wqkv" in layer["attn"] and "w1g" in layer["ffn"]
    batch = {"tokens": jnp.zeros((2, 8), jnp.int32)}
    logits, _ = T.forward(params, batch, cfg, train=False)
    assert logits.shape == (2, 8, cfg.vocab_padded)


def test_decode_step_layerwise_parity():
    params = T.init_params(jax.random.PRNGKey(0), CFG)
    tok = jnp.array([3, 5], dtype=jnp.int32)
    lo1, c1 = T.decode_step(params, tok, T.init_decode_cache(CFG, 2, 16), CFG)
    lo2, c2 = DP.decode_step_layerwise(
        params, tok, T.init_decode_cache(CFG, 2, 16), CFG)
    np.testing.assert_allclose(np.asarray(lo1), np.asarray(lo2),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(c1["pos"]), np.asarray(c2["pos"]))


def test_quantize_tree_stacked_layers():
    params = T.init_params(jax.random.PRNGKey(0), CFG)
    qp = DP.prepare_decode_params(params, CFG, fuse=True, bits=8)
    wqkv = qp["decoder"]["layers"]["attn"]["wqkv"]
    assert wqkv["Lq"].dtype == jnp.int8
    assert wqkv["Lq"].shape[0] == CFG.n_layers          # stacked factors ...
    assert wqkv["Ls"].shape[0] == CFG.n_layers          # ... per-layer scales
    assert wqkv["Ls"].shape[-2:] == (1, 1)
    assert qn.tree_weight_bytes(qp) < qn.tree_weight_bytes(params)
    # the stacked quantized tree drives the scanned decode step directly
    tok = jnp.array([3, 5], dtype=jnp.int32)
    lo, _ = T.decode_step(qp, tok, T.init_decode_cache(CFG, 2, 16), CFG)
    assert lo.shape == (2, CFG.vocab_padded)


# ---------------------------------------------------------------------------
# serving parity: int8 greedy decode vs fp32 through the continuous engine
# ---------------------------------------------------------------------------


def _engine_tokens(params, cfg, prompts, new_tokens, **engine_kw):
    from repro.serving import ContinuousBatchingEngine, GenerationConfig

    eng = ContinuousBatchingEngine(cfg, params, max_slots=4, page_size=8,
                                   max_len=64, **engine_kw)
    out = eng.generate(prompts, GenerationConfig(max_new_tokens=new_tokens))
    return np.asarray(out), eng


def test_serving_parity_int8_agreement():
    params = T.init_params(jax.random.PRNGKey(0), CFG)
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(9), (4, 8), 0, CFG.vocab))
    base, _ = _engine_tokens(params, CFG, prompts, 12)
    quant, eng = _engine_tokens(params, CFG, prompts, 12,
                                quantize="int8", fuse_projections=True)
    assert eng.weight_bits == 8
    agreement = float((base == quant).mean())
    assert agreement >= 0.95, f"int8 greedy agreement {agreement:.2%}"


def test_serving_int4_runs():
    params = T.init_params(jax.random.PRNGKey(0), CFG)
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(10), (2, 8), 0, CFG.vocab))
    out, eng = _engine_tokens(params, CFG, prompts, 6, quantize="int4")
    assert out.shape == (2, 6) and eng.weight_bits == 4


def test_cost_models_price_compressed_weights():
    from repro.serving.scheduler import CIMCostModel, HBMCostModel

    params = T.init_params(jax.random.PRNGKey(0), CFG)
    qp = DP.prepare_decode_params(params, CFG, fuse=True, bits=8)
    hb = HBMCostModel.from_params(CFG, params)
    hbq = HBMCostModel.from_params(CFG, qp)
    assert hbq.bytes_per_param < hb.bytes_per_param
    assert hbq.decode_step_ns(4, 64.0) < hb.decode_step_ns(4, 64.0)
    cim = CIMCostModel(CFG)
    cim4 = CIMCostModel(CFG, weight_bits=4, fused_proj=True)
    assert cim4.per_token_ns < cim.per_token_ns
