"""Jit'd public wrappers around the Pallas kernels.

``monarch_mm`` is what ``repro.core.linear`` dispatches to when
``MonarchSpec.backend == "pallas"``: it flattens leading batch dims, picks
the fused two-stage kernel when both factors fit the VMEM budget (the
DenseMap-analogue fast path), and otherwise runs the two ``bdmm`` stages
with the folded permutation in between.  ``monarch_mm_q`` is the same
dispatch over int8 / packed-int4 factors with per-block scales
(``repro.core.quant``), dequantized inside the kernels.

Backend/interpret and the VMEM-fit decision are resolved ONCE per
(shape, dtype) through a small ``lru_cache`` dispatch table — the decode hot
loop calls these per projection per token step, so none of that should be
recomputed per call.

On a CPU backend the kernels execute with ``interpret=True``; on a TPU they
compile through Mosaic.  ``tests/test_tpu_compile.py`` compiles each of them
for a described v5e at real model widths — the interpret-mode tests cannot
see tiling or VMEM refusals.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.bdmm import bdmm, bdmm_kmajor, bdmm_q_kmajor
from repro.kernels.monarch import (VMEM_BUDGET_BYTES, VMEM_LIMIT_BYTES,
                                   fused_fits, fused_working_set,
                                   monarch_fused, monarch_fused_q)


@functools.lru_cache(maxsize=None)
def _interpret() -> bool:
    return jax.default_backend() == "cpu"


@functools.lru_cache(maxsize=None)
def _dispatch(l_shape: tuple, r_shape: tuple, weight_bytes: float,
              scale_bytes: int = 0, dequant_bytes: float = 0,
              act_bytes: int = 4) -> tuple[bool, bool]:
    """(interpret, use_fused) for one (logical factor shapes, weight width,
    activation width) key.  ``l_shape``/``r_shape`` are the UNPACKED shapes;
    ``weight_bytes`` is the stored width (4/2 float, 1 int8, 0.5 packed
    int4) and ``dequant_bytes`` the in-kernel fp32 dequant temporaries of
    the quantized path.  Fusion also needs the kernel's whole working set
    (factors, token tiles, scratch) under the scoped VMEM it requests."""
    fused = (fused_fits(l_shape, r_shape, weight_bytes, scale_bytes,
                        dequant_bytes)
             and fused_working_set(l_shape, r_shape, weight_bytes,
                                   act_bytes) <= VMEM_LIMIT_BYTES)
    return _interpret(), fused


def _staged(xt, k, p, q, s, stage1, stage2):
    """Two block-major ``bdmm`` stages with the folded permutation between
    them: (T, k*p) -> (k, T, p) -> (k, T, q) -> (q, T, k) -> (q, T, s)."""
    u = stage1(jnp.transpose(xt.reshape(-1, k, p), (1, 0, 2)))
    y = stage2(jnp.transpose(u, (2, 1, 0)))
    return jnp.transpose(y, (1, 0, 2)).reshape(-1, q * s)


def monarch_mm(x: jax.Array, L: jax.Array, R: jax.Array) -> jax.Array:
    """y = x @ M for Monarch factors; x: (..., k*p) -> (..., q*s)."""
    *batch, din = x.shape
    k, q, p = L.shape
    _, s, _ = R.shape
    xt = x.reshape(-1, din)
    interp, fused = _dispatch(L.shape, R.shape, L.dtype.itemsize,
                              act_bytes=x.dtype.itemsize)
    if fused:
        y = monarch_fused(xt, L, R, interpret=interp)
    else:
        y = _staged(xt, k, p, q, s,
                    lambda u: bdmm_kmajor(u, L, interpret=interp),
                    lambda u: bdmm_kmajor(u, R, interpret=interp))
    return y.reshape(*batch, q * s)


def monarch_mm_q(x: jax.Array, Lq: jax.Array, Ls: jax.Array,
                 Rq: jax.Array, Rs: jax.Array) -> jax.Array:
    """Quantized Monarch matmul: int8/int4 factors + per-block scales,
    dequantized in VMEM (fp32 accumulate).  x: (..., k*p) -> (..., q*s)."""
    *batch, din = x.shape
    k = Ls.shape[0]
    q = Rs.shape[0]
    p = din // k
    s = Rq.shape[1]
    packed = Lq.shape[-1] != p
    weight_bytes = 0.5 if packed else 1
    xt = x.reshape(-1, din)
    # dequant_bytes=4 counts an fp32 copy of every weight next to the
    # pinned int8/int4 blocks; the kernel dequantizes one block at a time,
    # so this bounds its temporaries from above
    interp, fused = _dispatch((k, q, p), (q, s, k), weight_bytes,
                              scale_bytes=4 * (k + q), dequant_bytes=4,
                              act_bytes=x.dtype.itemsize)
    if fused:
        y = monarch_fused_q(xt, Lq, Ls, Rq, Rs, interpret=interp)
    else:
        y = _staged(xt, k, p, q, s,
                    lambda u: bdmm_q_kmajor(u, Lq, Ls, interpret=interp),
                    lambda u: bdmm_q_kmajor(u, Rq, Rs, interpret=interp))
    return y.reshape(*batch, q * s)


def bdmm_mm(x: jax.Array, w: jax.Array) -> jax.Array:
    """Public block-diagonal matmul: x (..., k, p) @ w (k, q, p)."""
    *batch, k, p = x.shape
    y = bdmm(x.reshape(-1, k, p), w, interpret=_interpret())
    return y.reshape(*batch, k, w.shape[1])


@functools.lru_cache(maxsize=None)
def paged_span_fits(span: int, n_heads: int, head_dim: int,
                    page_size: int, n_kv_heads: int, kv_bytes: float,
                    scale_bytes: int = 0, n_shards: int = 1) -> bool:
    """Does one grid step of the paged-attention span kernel fit VMEM?

    Sums ONE grid step's working set against the same budget the Monarch
    dispatch uses (the kernel asks the compiler for
    ``kernels.paged.SPAN_VMEM_LIMIT_BYTES``, room for every block
    double-buffered, so a "yes" here compiles): the query span block,
    BOTH gathered k/v page blocks at their **stored** width (``kv_bytes``: 4 fp32, 2 bf16, 1 int8), the
    per-(page, head) KV scale rows plus the fp32 dequant temporaries of
    the quantized path (``scale_bytes`` > 0 flags it — the kernel
    materializes fp32 copies of both pages next to the pinned int8
    blocks), the fp32 flash scratch (running max / normalizer /
    accumulator) and the output block.  ``n_shards`` is the KV-head split
    of a tensor-parallel pool: each shard's grid step gathers only its
    local ``n_kv_heads / n_shards`` page slice (and that slice's scale
    rows / dequant temporaries), so the KV-side terms divide.  Cached per
    shape because ``_paged_attend`` consults it per layer per engine
    step.  (Interpret mode stays the paged kernel's own decision —
    ``kernels.paged`` resolves it per backend.)"""
    n_shards = max(n_shards, 1)
    q_b = 4 * span * n_heads * head_dim
    kv_b = 2 * page_size * n_kv_heads * head_dim * kv_bytes / n_shards
    dequant_b = (2 * 4 * page_size * n_kv_heads * head_dim / n_shards
                 if scale_bytes else 0)
    scratch_b = 4 * (2 * span * n_heads + span * n_heads * head_dim)
    out_b = 4 * span * n_heads * head_dim
    total = (q_b + kv_b + dequant_b + scale_bytes / n_shards
             + scratch_b + out_b)
    return total <= VMEM_BUDGET_BYTES


def dispatch_cache_info():
    """Introspection for tests/benchmarks: the dispatch table's hit stats."""
    return _dispatch.cache_info()


# why a span step stayed on the dense-gather path ("kernel" = it didn't)
PAGED_DISPATCH_REASONS = ("kernel", "disabled", "softcap", "gqa_replicated",
                          "vmem")


@functools.lru_cache(maxsize=None)
def paged_dispatch(span: int, n_heads: int, head_dim: int, page_size: int,
                   n_kv_heads: int, kv_bytes: float, *,
                   quantized: bool = False, tp: int = 1, kv_shard: int = 1,
                   paged_kernel: bool = True,
                   softcap: bool = False) -> str:
    """THE kernel-vs-dense decision for one paged-attention span step.

    Returns ``"kernel"`` when the Pallas span kernel runs, else the reject
    reason (one of :data:`PAGED_DISPATCH_REASONS`): ``"disabled"`` — the
    model config never asked for it; ``"softcap"`` — logit soft-capping has
    no kernel implementation; ``"gqa_replicated"`` — a >1 "model" axis with
    a replicated KV pool (``kv_shard`` 1, i.e. ``n_kv_heads`` or
    ``n_heads`` not divisible by ``tp``), where only the dense gather
    partitions on the query-head axis; ``"vmem"`` — one grid step's working
    set spills :func:`paged_span_fits`.

    ``models.layers._paged_attend`` consults this at trace time and the
    serving engine re-derives the same decision per step for its dispatch
    counters — keeping the two in lockstep is the whole point of the shared
    helper.  At ``tp`` > 1 the fit is the honest PER-SHARD working set:
    query/scratch/output terms at ``n_heads / kv_shard`` heads, KV-side
    terms divided through ``n_shards=kv_shard``.
    """
    if not paged_kernel:
        return "disabled"
    if softcap:
        return "softcap"
    if tp > 1 and kv_shard != tp:
        return "gqa_replicated"
    shard = max(kv_shard, 1)
    fits = paged_span_fits(
        span, n_heads // shard, head_dim, page_size, n_kv_heads, kv_bytes,
        scale_bytes=2 * 4 * n_kv_heads if quantized else 0, n_shards=shard)
    return "kernel" if fits else "vmem"


__all__ = ["monarch_mm", "monarch_mm_q", "bdmm_mm", "paged_span_fits",
           "paged_dispatch", "PAGED_DISPATCH_REASONS", "dispatch_cache_info"]
