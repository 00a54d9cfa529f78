"""Compile the main-path kernels and the full-width serving step for a TPU
v5e that is described, not attached.

Interpret mode executes kernel bodies on the CPU and cannot see what the
TPU compiler refuses: blocks not aligned to the (8, 128) tiling, in-kernel
shape casts Mosaic has no layout for, more VMEM than a kernel may use.
These tests run the real compiler at CodeQwen1.5-7B widths (d_model 4096,
32 heads x 128, d_ff 13440, Monarch paper policy, 16-token pages).  The
topology is described inside a fixture, so only the worker that runs this
file loads the TPU compiler library.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.monarch import make_dims

CODEQWEN = get_config("codeqwen1_5-7b")
D, FF = CODEQWEN.d_model, CODEQWEN.d_ff
H, KV, HD = CODEQWEN.n_heads, CODEQWEN.n_kv_heads, CODEQWEN.hd
PAGE, SLOTS, MAX_LEN = 16, 8, 1024
# (din, dout) of the attention projections, FFN up/gate and FFN down
PROJECTIONS = {"attn": (D, D), "ffn_up": (D, FF), "ffn_down": (FF, D)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile cannot be read back from the persistent
    # cache without the device; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic():
    """Steer the kernel wrappers to Mosaic lowering: they choose interpret
    mode from ``jax.default_backend()``, which is the CPU here."""
    from repro.kernels import ops, paged

    saved = ops._interpret, paged._interpret
    ops._interpret = paged._interpret = lambda: False
    ops._dispatch.cache_clear()
    yield
    ops._interpret, paged._interpret = saved
    ops._dispatch.cache_clear()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _factors(name):
    dims = make_dims(*PROJECTIONS[name], policy="paper")
    return dims.k, dims.p, dims.q, dims.s


@pytest.mark.parametrize("tokens", [8, 1024])
@pytest.mark.parametrize("proj", sorted(PROJECTIONS))
def test_bdmm_compiles(one_chip, proj, tokens):
    from repro.kernels.bdmm import bdmm

    k, p, q, _ = _factors(proj)
    _compile(lambda x, w: bdmm(x, w, interpret=False),
             _sds(one_chip, (tokens, k, p), jnp.bfloat16),
             _sds(one_chip, (k, q, p), jnp.bfloat16))


@pytest.mark.parametrize("proj", sorted(PROJECTIONS))
def test_bdmm_q_compiles(one_chip, proj):
    from repro.kernels.bdmm import bdmm_q

    k, p, q, _ = _factors(proj)
    _compile(lambda x, w, s: bdmm_q(x, w, s, interpret=False),
             _sds(one_chip, (1024, k, p), jnp.bfloat16),
             _sds(one_chip, (k, q, p), jnp.int8),
             _sds(one_chip, (k, 1, 1), jnp.float32))


@pytest.mark.parametrize("tokens", [8, 1024])
@pytest.mark.parametrize("proj", sorted(PROJECTIONS))
def test_monarch_fused_compiles(one_chip, proj, tokens):
    from repro.kernels.monarch import monarch_fused

    k, p, q, s = _factors(proj)
    _compile(lambda x, L, R: monarch_fused(x, L, R, interpret=False),
             _sds(one_chip, (tokens, k * p), jnp.bfloat16),
             _sds(one_chip, (k, q, p), jnp.bfloat16),
             _sds(one_chip, (q, s, k), jnp.bfloat16))


@pytest.mark.parametrize("proj", sorted(PROJECTIONS))
def test_monarch_fused_q_compiles(one_chip, proj):
    from repro.kernels.monarch import monarch_fused_q

    k, p, q, s = _factors(proj)
    _compile(lambda x, a, b, c, d: monarch_fused_q(x, a, b, c, d,
                                                   interpret=False),
             _sds(one_chip, (1024, k * p), jnp.bfloat16),
             _sds(one_chip, (k, q, p), jnp.int8),
             _sds(one_chip, (k, 1, 1), jnp.float32),
             _sds(one_chip, (q, s, k), jnp.int8),
             _sds(one_chip, (q, 1, 1), jnp.float32))


def _largest_kernel_span(kv_dtype):
    """The longest power-of-two query span the dispatch sends to the
    kernel at these widths: a fit that says yes must compile."""
    from repro.core.quant import KV_DTYPE_BYTES
    from repro.kernels.ops import paged_dispatch

    span = 1
    while paged_dispatch(2 * span, H, HD, PAGE, KV, KV_DTYPE_BYTES[kv_dtype],
                         quantized=kv_dtype == "int8") == "kernel":
        span *= 2
    return span


@pytest.mark.parametrize("kv_dtype", ["bf16", "fp32", "int8"])
@pytest.mark.parametrize("longest", [False, True])
def test_paged_span_kernel_compiles(one_chip, kv_dtype, longest):
    from repro.kernels.paged import (_paged_attention_span,
                                     _paged_attention_span_q)

    span = _largest_kernel_span(kv_dtype) if longest else 1
    assert span >= 64 or not longest
    page_dt = {"bf16": jnp.bfloat16, "fp32": jnp.float32,
               "int8": jnp.int8}[kv_dtype]
    mp = MAX_LEN // PAGE
    n_pages = 1 + SLOTS * mp
    q = _sds(one_chip, (SLOTS, span, H, HD), jnp.bfloat16)
    pages = _sds(one_chip, (n_pages, PAGE, KV, HD), page_dt)
    tail = [_sds(one_chip, (SLOTS, mp), jnp.int32),
            _sds(one_chip, (SLOTS,), jnp.int32),
            _sds(one_chip, (SLOTS,), jnp.int32),
            _sds(one_chip, (), jnp.int32)]
    if kv_dtype == "int8":
        scales = _sds(one_chip, (n_pages, KV), jnp.float32)
        _compile(lambda *a: _paged_attention_span_q(*a, interpret=False),
                 q, pages, pages, scales, scales, *tail)
    else:
        _compile(lambda *a: _paged_attention_span(*a, interpret=False),
                 q, pages, pages, *tail)


@pytest.mark.parametrize("attention", ["dense", "kernel"])
@pytest.mark.parametrize("rows", [1, 32])
def test_full_width_mixed_step_compiles(one_chip, mosaic, rows, attention):
    """The engine's mixed step at full CodeQwen width, bf16 weights and KV,
    32 slots over 8 x 1024 tokens of pool, its span group of ``rows`` rows
    at the longest span the paged kernel takes: the dense gather with XLA's
    Monarch einsums, or the Pallas Monarch and paged kernels.  It fits one
    chip's 16 GiB."""
    from repro.models import transformer as T
    from repro.serving.engine import _mixed_step_jit

    slots = 32
    cfg = CODEQWEN
    if attention == "kernel":
        cfg = dataclasses.replace(
            CODEQWEN, paged_kernel=True,
            monarch=dataclasses.replace(CODEQWEN.monarch, backend="pallas"))
    params = jax.eval_shape(
        lambda k: T.serving_params(T.init_params(k, cfg), cfg),
        jax.random.PRNGKey(0))
    mp = MAX_LEN // PAGE
    pool = jax.eval_shape(lambda: T.init_paged_pool(cfg, 1 + SLOTS * mp,
                                                    PAGE))
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: _sds(one_chip, a.shape, a.dtype), tree)
    span = _largest_kernel_span("bf16")
    i32 = jnp.int32
    args = [((rows, span), i32), ((slots,), i32), ((slots,), bool),
            ((slots,), i32), ((slots,), i32), ((slots, mp), i32),
            ((slots,), i32), ((slots,), bool), ((slots,), jnp.float32),
            ((slots, 2), jnp.uint32)]
    compiled = _mixed_step_jit.lower(
        place(params), place(pool), *[_sds(one_chip, s, d) for s, d in args],
        cfg=cfg).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (attention == "kernel")
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert total < 16 * 2**30, total
