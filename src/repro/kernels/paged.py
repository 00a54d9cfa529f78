"""Pallas TPU kernel: paged-KV attention over variable-length query spans.

The continuous-batching engine stores KV in fixed-size pages owned by a
shared pool; a sequence's pages are scattered, so dense attention would
first have to gather them into a contiguous (B, T, KV, hd) buffer in HBM.
This kernel fuses the gather away: the grid walks (sequence, logical page)
and the k/v BlockSpec index maps read the *physical* page id from the
scalar-prefetched page table, so each step DMAs exactly one page into VMEM
and folds it into a flash-style running softmax.  No (B, T) KV
materialization, no host round-trips.

The unified engine iteration mixes decode tokens and prefill chunks in one
forward, so every sequence contributes a query *span*: row ``b`` carries
``span_len[b]`` queries at global positions ``start[b] + i``.  Masking is
causal within the span (query ``i`` sees keys at positions
``<= start[b] + i``) and window-limited like the decode path; rows with
``i >= span_len[b]`` are padding and return zeros.  A span of 1 is exactly
the old decode kernel; ``paged_attention`` keeps that single-query
signature as a thin wrapper.

Quantized KV pages (``core.quant``): when the pool stores int8 pages with
per-(page, head) fp32 scales, the kernel DMAs the int8 page AND its scale
row into VMEM and dequantizes in place (one cast + one multiply, fp32
accumulate) — a quarter of the fp32 page bytes per gathered key.  The
dequant is the same single op the host-side oracle runs, so the quantized
kernel is bitwise-identical to the fp32 kernel fed pre-dequantized pages.

Grid: (B, MP).  Scalar prefetch: page_table (B, MP), start (B,),
span_len (B,), window (1,).  Blocks fold the heads into the lane axis:
q/out (1, S, H*hd), a page (1, page, KV*hd), its scales (1, 1, KV); the
body walks the heads as static lane slices, so every op is 2-D.  Scratch:
per-(head, span) running max / normalizer and the (S, H*hd) accumulator,
persistent across the MP inner steps of one sequence.

On a CPU backend the kernel executes with ``interpret=True``; on a TPU it
compiles through Mosaic (``tests/test_tpu_compile.py`` compiles it for a
described v5e at real widths).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# scoped VMEM the span kernel asks the compiler for: twice the fit budget
# ``kernels.ops.paged_span_fits`` admits (the pipeline double-buffers every
# block) plus the per-head temporaries; v5e has 128 MiB of VMEM per core
SPAN_VMEM_LIMIT_BYTES = 32 * 2**20


def _span_attend(b, i, st_ref, sp_ref, win_ref, q_ref, kv_head,
                 o_ref, m_ref, l_ref, acc_ref, *, page_size: int,
                 n_heads: int, n_kv_heads: int):
    """One flash step over a single (sequence, page) grid cell: fold the
    page into the running softmax of every query head.

    Blocks are 2-D slabs with heads packed on the lane axis — q/out
    (S, H*hd), a page (page, KV*hd) — and each head is a static lane slice,
    so every op is a 2-D dot, a row reduction or an elementwise op.
    ``kv_head(j)`` returns KV head ``j`` of the page as fp32 (k, v), each
    (page, hd)."""
    @pl.when(i == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -1e30)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    S = q_ref.shape[1]
    hd = q_ref.shape[2] // n_heads
    g = n_heads // n_kv_heads
    pg = page_size
    t = i * pg + jax.lax.broadcasted_iota(jnp.int32, (S, pg), 1)
    q_pos = st_ref[b] + jax.lax.broadcasted_iota(jnp.int32, (S, pg), 0)
    ok = (t <= q_pos) & ((q_pos - t) < win_ref[0])           # (S, pg)
    okf = ok.astype(jnp.float32)

    for j in range(n_kv_heads):
        k, v = kv_head(j)
        for h in range(j * g, (j + 1) * g):
            cols = slice(h * hd, (h + 1) * hd)
            q = q_ref[0, :, cols].astype(jnp.float32)            # (S, hd)
            s = jax.lax.dot_general(
                q, k, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) / math.sqrt(hd)
            s = jnp.where(ok, s, -1e30)                         # (S, pg)
            m_prev = m_ref[h]                                   # (S, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # explicit ok-multiply: a fully-masked page would otherwise
            # contribute exp(-1e30 - (-1e30)) = 1 per key to the normalizer
            p = jnp.exp(s - m_new) * okf
            scale = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * scale + jnp.sum(p, axis=-1, keepdims=True)
            pv = jnp.dot(p, v, preferred_element_type=jnp.float32)
            acc_ref[:, cols] = acc_ref[:, cols] * scale + pv
            m_ref[h] = m_new

    @pl.when(i == pl.num_programs(1) - 1)
    def _emit():
        valid = jax.lax.broadcasted_iota(jnp.int32, (S, 1), 0) < sp_ref[b]
        for h in range(n_heads):
            cols = slice(h * hd, (h + 1) * hd)
            out = acc_ref[:, cols] / jnp.maximum(l_ref[h], 1e-30)
            o_ref[0, :, cols] = jnp.where(valid, out, 0.0).astype(o_ref.dtype)


def _paged_span_kernel(pt_ref, st_ref, sp_ref, win_ref, q_ref, k_ref, v_ref,
                       o_ref, m_ref, l_ref, acc_ref, *, page_size: int,
                       n_heads: int, n_kv_heads: int):
    hd = k_ref.shape[2] // n_kv_heads

    def kv_head(j):
        cols = slice(j * hd, (j + 1) * hd)
        return (k_ref[0, :, cols].astype(jnp.float32),
                v_ref[0, :, cols].astype(jnp.float32))

    _span_attend(pl.program_id(0), pl.program_id(1), st_ref, sp_ref,
                 win_ref, q_ref, kv_head, o_ref, m_ref, l_ref, acc_ref,
                 page_size=page_size, n_heads=n_heads, n_kv_heads=n_kv_heads)


def _paged_span_kernel_q(pt_ref, st_ref, sp_ref, win_ref, q_ref, k_ref,
                         v_ref, ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref,
                         *, page_size: int, n_heads: int, n_kv_heads: int):
    hd = k_ref.shape[2] // n_kv_heads

    def kv_head(j):
        # in-VMEM dequant: int8 rows x the page's per-head scale — the same
        # cast-multiply as core.quant.dequantize_kv_pages, so the result is
        # bitwise what the fp32 kernel sees on dequantized pages
        cols = slice(j * hd, (j + 1) * hd)
        return (k_ref[0, :, cols].astype(jnp.float32) * ks_ref[0, :, j:j + 1],
                v_ref[0, :, cols].astype(jnp.float32) * vs_ref[0, :, j:j + 1])

    _span_attend(pl.program_id(0), pl.program_id(1), st_ref, sp_ref,
                 win_ref, q_ref, kv_head, o_ref, m_ref, l_ref, acc_ref,
                 page_size=page_size, n_heads=n_heads, n_kv_heads=n_kv_heads)


def _span_call(kernel, q, k_pages, v_pages, scales, page_table, start,
               span_len, window, interpret):
    """Shared pallas_call: heads fold into the lane axis (free reshapes of
    the contiguous q / page buffers), per-(page, head) scales ride as
    (P, 1, KV) rows next to their page."""
    B, S, H, hd = q.shape
    P, pg, KV, _ = k_pages.shape
    MP = page_table.shape[1]
    page = pl.BlockSpec((1, pg, KV * hd),
                        lambda b, i, pt, st, sp, wn: (pt[b, i], 0, 0))
    scale = pl.BlockSpec((1, 1, KV),
                         lambda b, i, pt, st, sp, wn: (pt[b, i], 0, 0))
    span = pl.BlockSpec((1, S, H * hd),
                        lambda b, i, pt, st, sp, wn: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, MP),
        in_specs=[span, page, page] + [scale] * len(scales),
        out_specs=span,
        scratch_shapes=[
            pltpu.VMEM((H, S, 1), jnp.float32),
            pltpu.VMEM((H, S, 1), jnp.float32),
            pltpu.VMEM((S, H * hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(kernel, page_size=pg, n_heads=H, n_kv_heads=KV),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, H * hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=SPAN_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(page_table.astype(jnp.int32), start.astype(jnp.int32),
      span_len.astype(jnp.int32), window.reshape(1).astype(jnp.int32),
      q.reshape(B, S, H * hd), k_pages.reshape(P, pg, KV * hd),
      v_pages.reshape(P, pg, KV * hd),
      *[sc.astype(jnp.float32).reshape(P, 1, KV) for sc in scales])
    return out.reshape(B, S, H, hd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_attention_span(q, k_pages, v_pages, page_table, start, span_len,
                          window, *, interpret: bool):
    return _span_call(_paged_span_kernel, q, k_pages, v_pages, (),
                      page_table, start, span_len, window, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_attention_span_q(q, k_pages, v_pages, k_scales, v_scales,
                            page_table, start, span_len, window, *,
                            interpret: bool):
    return _span_call(_paged_span_kernel_q, q, k_pages, v_pages,
                      (k_scales, v_scales), page_table, start, span_len,
                      window, interpret)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"  # Mosaic-only lowering


def paged_attention_span(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                         page_table: jax.Array, start: jax.Array,
                         span_len: jax.Array, window: jax.Array,
                         k_scales: Optional[jax.Array] = None,
                         v_scales: Optional[jax.Array] = None) -> jax.Array:
    """q: (B, S, H, hd) query spans — row ``b``'s query ``i`` sits at global
    position ``start[b] + i`` and is valid iff ``i < span_len[b]`` (invalid
    rows return zeros); k/v_pages: (P, page, KV, hd); page_table: (B, MP);
    window: int32 scalar sliding window (huge value = global).
    ``k_scales``/``v_scales`` (P, KV): per-(page, head) fp32 scales of an
    int8 page pool — when given, pages are dequantized in VMEM (fp32
    accumulate) as they are gathered.
    Causal within the span: query ``i`` attends keys at positions
    ``<= start[b] + i`` only.  Returns (B, S, H, hd)."""
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be given together")
    if k_scales is not None:
        return _paged_attention_span_q(
            q, k_pages, v_pages, k_scales, v_scales, page_table, start,
            span_len, jnp.asarray(window), interpret=_interpret())
    return _paged_attention_span(q, k_pages, v_pages, page_table, start,
                                 span_len, jnp.asarray(window),
                                 interpret=_interpret())


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    page_table: jax.Array, lengths: jax.Array,
                    window: jax.Array,
                    k_scales: Optional[jax.Array] = None,
                    v_scales: Optional[jax.Array] = None) -> jax.Array:
    """Single-query decode special case (span of 1 per sequence).

    q: (B, H, hd) single-position queries; lengths: (B,) valid keys per row
    (current token included, so the query sits at position ``lengths - 1``).
    ``k_scales``/``v_scales``: optional (P, KV) int8-page scales, as in
    :func:`paged_attention_span`.  Returns (B, H, hd)."""
    B = q.shape[0]
    out = paged_attention_span(
        q[:, None], k_pages, v_pages, page_table,
        lengths.astype(jnp.int32) - 1, jnp.ones((B,), jnp.int32),
        jnp.asarray(window), k_scales=k_scales, v_scales=v_scales)
    return out[:, 0]


def paged_attention_span_sharded(q: jax.Array, k_pages: jax.Array,
                                 v_pages: jax.Array, page_table: jax.Array,
                                 start: jax.Array, span_len: jax.Array,
                                 window: jax.Array, mesh: jax.sharding.Mesh,
                                 k_scales: Optional[jax.Array] = None,
                                 v_scales: Optional[jax.Array] = None,
                                 axis: str = "model") -> jax.Array:
    """Span kernel under tensor parallelism: ``shard_map`` over ``axis``.

    Pallas custom calls don't partition under GSPMD — traced inside a >1
    "model" mesh the plain :func:`paged_attention_span` would force an
    all-gather of the sharded page buffers.  ``shard_map`` sidesteps GSPMD
    entirely: each shard runs the SAME kernel on its local KV-head slice of
    the page pool (q heads, page KV rows and scale rows all split on the
    head axis; grid, page table and flash loop unchanged), and no
    collective is needed because attention heads never mix — outputs
    concatenate on the head axis, which is exactly the sharding the
    surrounding layer keeps q in.  The page axis is never sharded (the
    ``DeviceKV`` contract), so every shard sees the full page table and its
    span writes stay shard-local.

    Arguments are as in :func:`paged_attention_span`, plus the engine mesh.
    Shapes are GLOBAL; the per-shard kernel sees ``H / tp`` query heads and
    ``KV / tp`` page heads, so both must divide by the ``axis`` size (the
    caller gates on that — GQA-replicated pools stay on the dense path).
    Mesh axes other than ``axis`` (the "data" axis) are untouched: inputs
    are replicated over them and each slice computes identical outputs.
    ``check_vma=False`` because pallas_call defeats shard_map's replication
    checker, not because anything is unreplicated."""
    from jax.sharding import PartitionSpec as P

    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be given together")
    tp = dict(mesh.shape)[axis]
    if q.shape[2] % tp or k_pages.shape[2] % tp:
        raise ValueError(
            f"heads {q.shape[2]}/KV {k_pages.shape[2]} must divide the "
            f"{axis!r} axis size {tp}")
    heads = P(None, None, axis, None)
    rep = P()
    win = jnp.asarray(window, jnp.int32)
    interp = _interpret()

    if k_scales is not None:
        def body(q, kp, vp, ks, vs, pt, st, sp, wn):
            return _paged_attention_span_q(q, kp, vp, ks, vs, pt, st, sp,
                                           wn, interpret=interp)

        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(heads, heads, heads, P(None, axis),
                                     P(None, axis), rep, rep, rep, rep),
                           out_specs=heads, check_vma=False)
        return fn(q, k_pages, v_pages, k_scales, v_scales,
                  page_table.astype(jnp.int32), start.astype(jnp.int32),
                  span_len.astype(jnp.int32), win)

    def body(q, kp, vp, pt, st, sp, wn):
        return _paged_attention_span(q, kp, vp, pt, st, sp, wn,
                                     interpret=interp)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(heads, heads, heads, rep, rep, rep, rep),
                       out_specs=heads, check_vma=False)
    return fn(q, k_pages, v_pages, page_table.astype(jnp.int32),
              start.astype(jnp.int32), span_len.astype(jnp.int32), win)


def paged_attention_sharded(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, page_table: jax.Array,
                            lengths: jax.Array, window: jax.Array,
                            mesh: jax.sharding.Mesh,
                            k_scales: Optional[jax.Array] = None,
                            v_scales: Optional[jax.Array] = None,
                            axis: str = "model") -> jax.Array:
    """Single-query decode under tensor parallelism (span of 1 per row),
    mirroring :func:`paged_attention` over :func:`paged_attention_span_sharded`."""
    B = q.shape[0]
    out = paged_attention_span_sharded(
        q[:, None], k_pages, v_pages, page_table,
        lengths.astype(jnp.int32) - 1, jnp.ones((B,), jnp.int32),
        jnp.asarray(window), mesh, k_scales=k_scales, v_scales=v_scales,
        axis=axis)
    return out[:, 0]


__all__ = ["paged_attention", "paged_attention_span",
           "paged_attention_sharded", "paged_attention_span_sharded"]
