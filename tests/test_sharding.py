"""Unit tests for the parameter partition rules (sharding/params.py) and
the mesh/TP plumbing that does not need real devices.

``AbstractMesh`` gives the rules a device-less 8-way "model" axis, so the
suffix matching, leading-dim padding and divisibility guard are exercised
even on the single-device CI runner; tests that need actual shards live in
tests/test_tp_serving.py behind device-count skips.
"""

import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.models.config import ModelConfig
from repro.sharding.params import spec_for


@pytest.fixture(scope="module")
def mesh8():
    return AbstractMesh((1, 8), ("data", "model"))


# -- suffix-rule matching on nested paths ------------------------------------

def test_attention_projections_megatron_pair(mesh8):
    # column-parallel in, row-parallel out: one all-reduce per layer
    assert spec_for("decoder/layers/attn/wq/w", (128, 128), mesh8) == \
        P("data", "model")
    assert spec_for("decoder/layers/attn/wv/w", (128, 128), mesh8) == \
        P("data", "model")
    assert spec_for("decoder/layers/attn/wo/w", (128, 128), mesh8) == \
        P("model", "data")


def test_monarch_factor_rules_win_over_projection_rules(mesh8):
    # "/L" precedes ("wq", "w") in the rule list, so a Monarch factor under
    # an attention projection shards as a factor (stage-1 block-rows over
    # "model"), not as a dense weight
    assert spec_for("decoder/layers/attn/wq/L", (8, 16, 16), mesh8) == \
        P("model", None, "data")
    assert spec_for("decoder/layers/attn/wq/R", (16, 16, 8), mesh8) == \
        P(None, "data", "model")


def test_fused_keys_ride_existing_rules_by_substring(mesh8):
    # fuse.py emits wqkv / wkv / w1g — substring containment means they hit
    # the wq / wk / w1 rules without fusion-specific entries
    assert spec_for("decoder/layers/attn/wqkv/w", (128, 384), mesh8) == \
        P("data", "model")
    assert spec_for("decoder/layers/attn/wkv/w", (128, 256), mesh8) == \
        P("data", "model")
    assert spec_for("decoder/layers/ffn/w1g/w", (128, 512), mesh8) == \
        P("data", "model")


def test_embedding_rules(mesh8):
    assert spec_for("embedding/table", (512, 128), mesh8) == \
        P("model", "data")
    assert spec_for("embedding/unembed", (128, 512), mesh8) == \
        P("data", "model")


def test_unmatched_paths_replicate(mesh8):
    assert spec_for("decoder/layers/ln1/scale", (128,), mesh8) == P()
    assert spec_for("ln_f/scale", (128,), mesh8) == P()


# -- leading-dim None padding -------------------------------------------------

def test_layer_stacked_leaves_pad_leading_dims(mesh8):
    # vmap-initialized trees carry a leading layer axis the trailing-dim
    # rules never name: it must pad to None, not shift the spec
    assert spec_for("decoder/layers/attn/wq/w", (4, 128, 128), mesh8) == \
        P(None, "data", "model")
    assert spec_for("decoder/layers/attn/wq/L", (4, 8, 16, 16), mesh8) == \
        P(None, "model", None, "data")


def test_rule_longer_than_shape_replicates(mesh8):
    # a scalar-ish leaf that happens to match a multi-dim rule replicates
    # instead of raising
    assert spec_for("decoder/layers/attn/wo/w", (128,), mesh8) == P()


# -- divisibility guard -------------------------------------------------------

def test_minicpm_vocab_stays_unsharded_on_8way_axis(mesh8):
    # 122753 is prime-ish w.r.t. 8: the vocab dim must stay replicated
    # while the d_model dim keeps its axis
    assert spec_for("embedding/unembed", (128, 122753), mesh8) == \
        P("data", None)
    assert spec_for("embedding/table", (122753, 128), mesh8) == \
        P(None, "data")


def test_divisibility_guard_is_per_dim(mesh8):
    # only the offending dim drops its axis, others keep theirs
    assert spec_for("decoder/layers/attn/wq/w", (128, 129), mesh8) == \
        P("data", None)


def test_missing_mesh_axis_drops_to_none():
    mesh = AbstractMesh((8,), ("model",))  # no "data" axis at all
    assert spec_for("decoder/layers/attn/wq/w", (128, 128), mesh) == \
        P(None, "model")


# -- mesh construction (launch/mesh.py) ---------------------------------------

def test_make_host_mesh_rejects_non_dividing_model_axis():
    from repro.launch.mesh import make_host_mesh

    n = len(jax.devices())
    with pytest.raises(ValueError, match="does not divide"):
        make_host_mesh(model=n + 1)


def test_make_host_mesh_model_1_always_works():
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(model=1)
    assert dict(mesh.shape)["model"] == 1
    assert dict(mesh.shape)["data"] == len(jax.devices())


# -- kv shard sizing ----------------------------------------------------------

def test_kv_shard_size_divisibility(mesh8):
    from repro.serving.device_kv import kv_shard_size

    gqa = ModelConfig(name="t", d_model=128, n_layers=2, n_heads=8,
                      n_kv_heads=2, d_ff=256, vocab=512, dtype="float32")
    mha = ModelConfig(name="t", d_model=128, n_layers=2, n_heads=8,
                      n_kv_heads=8, d_ff=256, vocab=512, dtype="float32")
    assert kv_shard_size(mha, None) == 1
    assert kv_shard_size(mha, mesh8) == 8
    # 2 KV heads on an 8-way model axis: replicated, never uneven
    assert kv_shard_size(gqa, mesh8) == 1


# -- paged_span_fits per-shard accounting -------------------------------------

def test_paged_span_fits_divides_kv_terms_by_shards():
    from repro.kernels.ops import VMEM_BUDGET_BYTES, paged_span_fits

    # pick a KV page block that busts VMEM whole but fits split 8 ways
    hd, kv_bytes = 128, 4
    page = 1
    n_kv = 2
    while 2 * page * n_kv * hd * kv_bytes <= VMEM_BUDGET_BYTES:
        page *= 2
    assert not paged_span_fits(1, 8, hd, page, n_kv, kv_bytes)
    assert paged_span_fits(1, 8, hd, page, n_kv, kv_bytes, n_shards=8)


# -- cost-model TP pricing ----------------------------------------------------

CFG = ModelConfig(name="t", d_model=128, n_layers=2, n_heads=8,
                  n_kv_heads=8, d_ff=256, vocab=512, dtype="float32")


def test_tp_allreduce_bytes_formula():
    from repro.serving.scheduler import tp_allreduce_bytes_per_token

    assert tp_allreduce_bytes_per_token(CFG, 1) == 0.0
    b8 = tp_allreduce_bytes_per_token(CFG, 8)
    # 2 reduces/layer * n_layers * d_model fp32 * ring factor
    assert b8 == 2.0 * 7 / 8 * 128 * 4.0 * 2 * 2
    assert tp_allreduce_bytes_per_token(CFG, 2) < b8  # ring factor grows


def test_hbm_cost_model_tp_pricing():
    from repro.serving.scheduler import HBMCostModel

    m1 = HBMCostModel.from_model_config(CFG, kv_dtype="fp32", tp=1)
    m8 = HBMCostModel.from_model_config(CFG, kv_dtype="fp32", tp=8)
    assert m8.kv_shard == 8 and m8.allreduce_bytes_per_token > 0
    b1 = m1.shard_decode_bytes_per_token(256.0, n_seqs=8)
    b8 = m8.shard_decode_bytes_per_token(256.0, n_seqs=8)
    assert b8["weight_kv_bytes"] < b1["weight_kv_bytes"]
    assert b8["weight_bytes"] == pytest.approx(b1["weight_bytes"] / 8)
    assert b8["kv_bytes"] == pytest.approx(b1["kv_bytes"] / 8)
    # the all-reduce term is priced: a zero-bandwidth bus would dominate
    assert m8.decode_step_ns(8, 256.0) > 0
    slow = HBMCostModel.from_model_config(
        CFG, kv_dtype="fp32", tp=8, reduce_bandwidth_gbps=1e-6)
    assert slow.decode_step_ns(8, 256.0) > m8.decode_step_ns(8, 256.0)


def test_hbm_cost_model_tp_kv_shard_guard():
    from repro.serving.scheduler import HBMCostModel

    gqa = ModelConfig(name="t", d_model=128, n_layers=2, n_heads=8,
                      n_kv_heads=2, d_ff=256, vocab=512, dtype="float32")
    m = HBMCostModel.from_model_config(gqa, tp=8)
    assert m.tp == 8 and m.kv_shard == 1  # KV replicated, weights split


def test_cim_cost_model_tp_pricing():
    from repro.serving.scheduler import CIMCostModel

    m1 = CIMCostModel(CFG, tp=1)
    m8 = CIMCostModel(CFG, tp=8)
    assert m8.kv_shard == 8
    b1 = m1.shard_decode_bytes_per_token(256.0, n_seqs=8)
    b8 = m8.shard_decode_bytes_per_token(256.0, n_seqs=8)
    assert b8["weight_kv_bytes"] < b1["weight_kv_bytes"]
    # reduction bus is priced: per-token time does not divide by a full 8x
    assert m8.per_token_ns > m1.per_token_ns / 8
    assert m8.attn_dpu_ns_per_key == pytest.approx(
        m1.attn_dpu_ns_per_key / 8)
