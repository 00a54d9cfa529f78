"""Core transformer building blocks: norms, RoPE, GQA attention, FFNs, MoE.

Every parameterized matmul routes through ``repro.core.linear`` so the
paper's Monarch factorization is a global switch (``cfg.monarch``).
Attention-score / AV matmuls are non-parameterized and stay dense, exactly
as in the paper (Sec. III-A, Fig. 2b NonPara-Matmul).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.linear import MonarchSpec, linear_apply, linear_init
from repro.models.config import ModelConfig, MoEConfig
from repro.sharding import current_mesh, logical


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def norm_init(d: int, kind: str) -> dict:
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,))}
    return {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))}


def norm_apply(params: dict, x: jax.Array, kind: str, eps: float = 1e-6) -> jax.Array:
    with jax.named_scope("norm"):
        xf = x.astype(jnp.float32)
        if kind == "rmsnorm":
            var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
            out = xf * jax.lax.rsqrt(var + eps) * (1.0 + 0.0 + params["scale"])
        else:
            mean = jnp.mean(xf, axis=-1, keepdims=True)
            var = jnp.var(xf, axis=-1, keepdims=True)
            out = ((xf - mean) * jax.lax.rsqrt(var + eps) * params["scale"]
                   + params["bias"])
        return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd) with positions (..., S) or (S,)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, optional local window / logit softcap / cross-attention)
# ---------------------------------------------------------------------------


def attention_init(key, cfg: ModelConfig, d_in: Optional[int] = None) -> dict:
    d = d_in or cfg.d_model
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    spec = cfg.monarch
    wo = linear_init(ks[3], h * hd, d, spec=spec,
                     w_init_scale=1.0 / math.sqrt(2 * max(cfg.n_layers, 1)))
    if cfg.fused_proj:
        # decode fast path, built fused at init: one widened projection per
        # weight visit (QKV share the input — the CIM co-activation analogue)
        if h == kv:
            return {"wqkv": linear_init(ks[0], d, (h + 2 * kv) * hd,
                                        spec=spec),
                    "wo": wo}
        return {"wq": linear_init(ks[0], d, h * hd, spec=spec),
                "wkv": linear_init(ks[1], d, 2 * kv * hd, spec=spec),
                "wo": wo}
    return {
        "wq": linear_init(ks[0], d, h * hd, spec=spec),
        "wk": linear_init(ks[1], d, kv * hd, spec=spec),
        "wv": linear_init(ks[2], d, kv * hd, spec=spec),
        "wo": wo,
    }


def _softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def _sdpa(q, k, v, mask, softcap, dtype, fast_scores: bool = False):
    """q: (B,S,H,hd) k/v: (B,T,KV,hd); GQA via head grouping."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    g = H // KV
    q = q.reshape(B, S, KV, g, hd)
    score_dtype = jnp.bfloat16 if fast_scores else jnp.float32
    scores = jnp.einsum("bskgh,btkh->bkgst", q, k).astype(score_dtype)
    scores = scores / math.sqrt(hd)
    scores = _softcap(scores, softcap)
    neg = jnp.asarray(-3e4 if fast_scores else -1e30, score_dtype)
    # additive mask: one fused add instead of a select on a full f32 tensor
    scores = scores + jnp.where(mask, jnp.zeros((), score_dtype), neg)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(B, S, H, hd)


def _sdpa_chunked(q, k, v, softcap, dtype, chunk: int, window, bidir: bool):
    """KV-chunked (flash-style) self-attention for train/prefill: running
    max/sum over KV chunks bounds score materialization to (S x chunk)
    instead of (S x S) — the fits-on-chip fix for 32k prefill (Sec. Perf H1).
    Causal (+ optional sliding window) masking computed per chunk."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    g = H // KV
    T = k.shape[1]
    C = min(chunk, T)
    assert T % C == 0, (T, C)
    nC = T // C
    qh = q.reshape(B, S, KV, g, hd)
    kc = jnp.moveaxis(k.reshape(B, nC, C, KV, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nC, C, KV, hd), 1, 0)
    qi = jnp.arange(S)[:, None]

    def step(carry, inp):
        m, l, acc = carry
        kcb, vcb, c0 = inp
        s = jnp.einsum("bskgh,btkh->bkgst", qh, kcb).astype(jnp.float32)
        s = _softcap(s / math.sqrt(hd), softcap)
        kj = c0 + jnp.arange(C)[None, :]
        ok = jnp.ones((S, C), bool) if bidir else (kj <= qi)
        if window is not None:
            ok &= (qi - kj) < window
        s = s + jnp.where(ok, 0.0, -1e30)[None, None, None]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        scale = jnp.exp(m - m_new)
        l = l * scale + jnp.sum(p, axis=-1)
        acc = acc * scale[..., None] + jnp.einsum(
            "bkgst,btkh->bkgsh", p.astype(dtype), vcb).astype(jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((B, KV, g, S), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, KV, g, S), jnp.float32)
    a0 = jnp.zeros((B, KV, g, S, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (kc, vc, jnp.arange(nC) * C))
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(dtype)
    return jnp.moveaxis(out, 3, 1).reshape(B, S, H, hd)


def causal_mask(S: int, T: int, offset: int, window: Optional[int]) -> jax.Array:
    """(1,1,1,S,T) boolean; query i attends key j iff j <= i+offset and
    (window is None or i+offset - j < window)."""
    qi = jnp.arange(S)[:, None] + offset
    kj = jnp.arange(T)[None, :]
    m = kj <= qi
    if window is not None:
        m = m & (qi - kj < window)
    return m[None, None, None]


def attention_apply(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    window=None,
    cache: Optional[dict] = None,
    pos: Optional[jax.Array] = None,
    page_table: Optional[jax.Array] = None,
    span_len: Optional[jax.Array] = None,
    write_start: Optional[jax.Array] = None,
    kv_input: Optional[jax.Array] = None,
    bidir: bool = False,
    backend: str = "einsum",
) -> tuple[jax.Array, Optional[dict]]:
    """Self- (or cross-, with ``kv_input``) attention.

    ``window``: None for full attention, or an int / traced scalar for a
    sliding window (traced per-layer values let local/global alternation
    share one scanned stack).
    ``cache``: either the contiguous ring cache {"k": (B,T,KV,hd), "v": ...}
    or a paged cache {"k_pages": (P,page,KV,hd), "v_pages": ...} addressed
    through ``page_table`` (B, max_pages).  Both accept S >= 1 new tokens per
    row (S > 1 is the chunked prefill / mixed-step path), written at
    positions ``pos[b] + arange(S)``.
    ``span_len``: (B,) valid new tokens per row of the paged path — rows may
    carry spans shorter than S (the mixed decode + prefill-chunk batch);
    positions at or beyond ``span_len[b]`` write to the sink page instead of
    the sequence's tables.  None means every row's span is the full S.
    ``write_start``: (B,) copy-on-write fork point per row of the paged
    path — global positions below it sit in refcount-shared prefix pages
    and their writes are redirected to the sink (shared history is
    immutable; reads still gather through the page table).
    Returns (out, updated_cache).  Its operations carry the ``attention``
    scope, its projections the nested ``linear`` one.
    """
    with jax.named_scope("attention"):
        B, S, d = x.shape
        h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        dtype = x.dtype

        kv_src = x if kv_input is None else kv_input
        Skv = kv_src.shape[1]
        if "wqkv" in params:
            # fused projection: one weight visit computes q, k and v (exact
            # concatenation of the separate outputs — see models/fuse.py)
            assert kv_input is None, "fused QKV is self-attention only"
            qd, kd = h * hd, kv * hd
            qkv = linear_apply(params["wqkv"], x, backend=backend)
            q = qkv[..., :qd].reshape(B, S, h, hd)
            k = qkv[..., qd:qd + kd].reshape(B, Skv, kv, hd)
            v = qkv[..., qd + kd:].reshape(B, Skv, kv, hd)
        elif "wkv" in params:
            q = linear_apply(params["wq"], x, backend=backend).reshape(B, S, h, hd)
            kvh = linear_apply(params["wkv"], kv_src, backend=backend)
            k = kvh[..., :kv * hd].reshape(B, Skv, kv, hd)
            v = kvh[..., kv * hd:].reshape(B, Skv, kv, hd)
        else:
            q = linear_apply(params["wq"], x, backend=backend).reshape(B, S, h, hd)
            k = linear_apply(params["wk"], kv_src, backend=backend).reshape(
                B, Skv, kv, hd)
            v = linear_apply(params["wv"], kv_src, backend=backend).reshape(
                B, Skv, kv, hd)

        if pos is None:
            q_pos = jnp.arange(S)
            k_pos = jnp.arange(Skv)
        else:  # cached: per-row start position, S consecutive new tokens
            q_pos = pos.reshape(B, 1) + jnp.arange(S)[None, :]
            k_pos = q_pos
        if not bidir and kv_input is None:
            q = rope(q, q_pos, cfg.rope_theta)
            k = rope(k, k_pos, cfg.rope_theta)
        if cfg.qk_norm:
            q = q / (jnp.linalg.norm(q, axis=-1, keepdims=True) + 1e-6)
            k = k / (jnp.linalg.norm(k, axis=-1, keepdims=True) + 1e-6)

        q = logical(q, "batch", "seq", "heads", "head_dim")
        kv_seq = "seq" if cache is None else "kv_seq"
        k = logical(k, "batch", kv_seq, "kv_heads", "head_dim")
        v = logical(v, "batch", kv_seq, "kv_heads", "head_dim")

        new_cache = None
        if cache is not None and "k_pages" in cache:
            out, new_cache = _paged_attend(
                q, k, v, cache, page_table, q_pos, cfg, window, dtype,
                span_len=span_len, write_start=write_start)
        elif cache is not None:
            # write the S new k/v rows at pos..pos+S-1 into the ring cache,
            # attend each query over the cache under its own causal horizon
            ck, cv = cache["k"], cache["v"]
            T = ck.shape[1]
            rows = jnp.arange(B)[:, None]
            ck = ck.at[rows, q_pos].set(k)
            cv = cv.at[rows, q_pos].set(v)
            new_cache = {"k": ck, "v": cv}
            kj = jnp.arange(T)[None, None, :]
            valid = kj <= q_pos[..., None]  # (B,S,T)
            if window is not None:
                valid &= (q_pos[..., None] - kj) < window
            mask = valid[:, None, None]  # (B,1,1,S,T)
            out = _sdpa(q, ck, cv, mask, cfg.logit_softcap, dtype,
                        fast_scores=cfg.fast_decode_scores)
        elif (cfg.attn_chunk is not None and kv_input is None
              and Skv > cfg.attn_chunk):
            out = _sdpa_chunked(q, k, v, cfg.logit_softcap, dtype,
                                cfg.attn_chunk, window, bidir)
        else:
            if bidir:
                mask = jnp.ones((1, 1, 1, S, Skv), dtype=bool)
            elif kv_input is not None:  # cross-attention: attend everything
                mask = jnp.ones((1, 1, 1, S, Skv), dtype=bool)
            else:
                mask = causal_mask(S, Skv, 0, window)
            out = _sdpa(q, k, v, mask, cfg.logit_softcap, dtype,
                        fast_scores=cfg.fast_decode_scores)

        out = out.reshape(B, S, h * hd)
        out = linear_apply(params["wo"], out, backend=backend)
        return logical(out, "batch", "seq", "embed"), new_cache


def attention_cache_init(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    kv, hd = cfg.n_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((batch, max_len, kv, hd), dtype=dtype),
        "v": jnp.zeros((batch, max_len, kv, hd), dtype=dtype),
    }


def paged_cache_init(cfg: ModelConfig, n_pages: int, page_size: int, dtype,
                     kv_dtype: Optional[str] = None) -> dict:
    """One layer's share of the paged KV pool: ``n_pages`` fixed-size pages.

    Unlike the ring cache there is no batch dimension — sequences own
    disjoint page sets through their page tables, so one physical pool
    serves every slot of the continuous-batching engine.

    ``kv_dtype`` selects the stored page width: None keeps the model
    ``dtype`` (legacy behavior), "fp32"/"bf16" store pages at that float
    width, and "int8" stores int8 pages plus one fp32 scale per
    (page, kv_head) for K and V independently (``core.quant``) — the
    parallel scale buffers ride the same pytree, so COW page copies and the
    scanned layer stack thread them like any other pool array.
    """
    kv, hd = cfg.n_kv_heads, cfg.hd
    if kv_dtype is None:
        page_dtype = dtype
    elif kv_dtype == "fp32":
        page_dtype = jnp.float32
    elif kv_dtype == "bf16":
        page_dtype = jnp.bfloat16
    elif kv_dtype == "int8":
        page_dtype = jnp.int8
    else:
        raise ValueError(f"unknown kv_dtype {kv_dtype!r}")
    cache = {
        "k_pages": jnp.zeros((n_pages, page_size, kv, hd), dtype=page_dtype),
        "v_pages": jnp.zeros((n_pages, page_size, kv, hd), dtype=page_dtype),
    }
    if kv_dtype == "int8":
        cache["k_scales"] = jnp.zeros((n_pages, kv), jnp.float32)
        cache["v_scales"] = jnp.zeros((n_pages, kv), jnp.float32)
    return cache


def _paged_attend(q, k, v, cache, page_table, q_pos, cfg: ModelConfig,
                  window, dtype, span_len=None, write_start=None):
    """Write S new k/v rows through the page table, attend over the gathered
    pages.

    q: (B,S,H,hd); k/v: (B,S,KV,hd); cache pages: (P, page, KV, hd);
    page_table: (B, MP) physical page ids; q_pos: (B,S) global positions;
    span_len: optional (B,) valid-token count per row (None = full S);
    write_start: optional (B,) per-row COW fork point — writes at global
    positions below it are redirected to the sink.
    Logical page ``g // page`` of global position ``g`` maps to physical page
    ``page_table[b, g // page]``.  Unallocated table entries point at the
    reserved sink page 0; they are never attended because the causal mask
    only admits keys at positions <= q_pos.  Positions past a row's span are
    padding — their writes are redirected to the sink page so they can never
    land in another logical position's live page.  Positions below a row's
    ``write_start`` sit in prefix pages shared (refcounted) with other
    sequences — equally redirected, so span writes are provably confined to
    exclusively-owned pages no matter what spans the host schedules.

    An int8 pool (cache carries ``k_scales``/``v_scales``, (P, KV) fp32
    per-(page, head) scales) quantizes the fresh span rows on device before
    the page write and dequantizes on read — in-kernel for the Pallas path,
    on the gathered blocks for the dense fallback.  The write-mask
    semantics above are unchanged: the same sink redirect guards the scale
    updates, so shared pages and their scales stay immutable.
    """
    kp, vp = cache["k_pages"], cache["v_pages"]
    quantized = "k_scales" in cache
    pg = kp.shape[1]
    B, S = q_pos.shape
    phys = jnp.take_along_axis(page_table, q_pos // pg, axis=1)  # (B,S)
    off = q_pos % pg
    if span_len is not None or write_start is not None:
        valid = jnp.ones((B, S), bool)
        if span_len is not None:
            valid &= jnp.arange(S)[None, :] < span_len[:, None]  # (B,S)
        if write_start is not None:
            valid &= q_pos >= write_start[:, None]
        phys = jnp.where(valid, phys, 0)  # page 0 is the reserved sink
    ks = vs = None
    if quantized:
        # quantize the freshly computed span rows on device before the page
        # write: per-(page, head) scales grow to cover the new rows (stored
        # rows rescale where needed; untouched — i.e. every shared/committed
        # — page comes out bit-identical, see core.quant.quantize_kv_write).
        # The sink redirect above applies to the scale updates too, so
        # shared-prefix pages' scales are as immutable as their rows.
        from repro.core.quant import quantize_kv_write  # lazy: optional path

        # deduplicated rescale set: the span's logical page RANGE from the
        # page table (ceil(S/pg)+1 entries/row, vs S per-position entries).
        # It covers every non-sink page ``phys`` can name — including pages
        # whose boundary positions were sink-redirected — and any extras
        # (stalled rows, shared pages) rescale by exactly 1.0, a bitwise
        # no-op.
        nK = (S + pg - 1) // pg + 1
        jcols = jnp.clip(q_pos[:, :1] // pg + jnp.arange(nK)[None, :],
                         0, page_table.shape[1] - 1)
        resc = jnp.take_along_axis(page_table, jcols, axis=1)  # (B, nK)
        kp, ks = quantize_kv_write(kp, cache["k_scales"], phys, off, k,
                                   rescale_phys=resc)
        vp, vs = quantize_kv_write(vp, cache["v_scales"], phys, off, v,
                                   rescale_phys=resc)
        new_cache = {"k_pages": kp, "v_pages": vp,
                     "k_scales": ks, "v_scales": vs}
    else:
        kp = kp.at[phys, off].set(k.astype(kp.dtype))
        vp = vp.at[phys, off].set(v.astype(vp.dtype))
        new_cache = {"k_pages": kp, "v_pages": vp}

    # kernel-vs-dense dispatch: ONE shared, cached decision
    # (``kernels.ops.paged_dispatch`` — the serving engine re-derives the
    # same call per step for its dispatch counters).  Under a >1 "model"
    # axis the kernel runs shard_mapped: each shard keeps its local KV-head
    # slice of the page buffers and scale rows (the page axis is never
    # sharded, so span writes stay shard-local per the DeviceKV contract),
    # and the VMEM fit is the honest per-shard working set via
    # ``paged_span_fits(n_shards=kv_shard)``.  A GQA-replicated pool
    # (``kv_shard`` 1 at tp > 1) stays on the dense gather below, which
    # partitions on the query-head axis instead.
    mesh = current_mesh()
    tp = 1 if mesh is None else dict(mesh.shape).get("model", 1)
    KV = kp.shape[2]
    H = q.shape[2]
    kv_shard = tp if tp > 1 and KV % tp == 0 and H % tp == 0 else 1

    from repro.kernels.ops import paged_dispatch

    decision = paged_dispatch(
        S, H, q.shape[3], pg, KV, kp.dtype.itemsize, quantized=quantized,
        tp=tp, kv_shard=kv_shard, paged_kernel=cfg.paged_kernel,
        softcap=cfg.logit_softcap is not None)
    if decision == "kernel":
        from repro.kernels.paged import (  # lazy: optional path
            paged_attention, paged_attention_sharded, paged_attention_span,
            paged_attention_span_sharded)

        win = jnp.asarray(
            1_000_000_000 if window is None else window, jnp.int32)
        if S == 1 and span_len is None:
            if tp > 1:
                out = paged_attention_sharded(q[:, 0], kp, vp, page_table,
                                              q_pos[:, 0] + 1, win, mesh,
                                              k_scales=ks, v_scales=vs)
            else:
                out = paged_attention(q[:, 0], kp, vp, page_table,
                                      q_pos[:, 0] + 1, win,
                                      k_scales=ks, v_scales=vs)
            return out[:, None], new_cache
        sp = jnp.full((B,), S, jnp.int32) if span_len is None else span_len
        if tp > 1:
            out = paged_attention_span_sharded(q, kp, vp, page_table,
                                               q_pos[:, 0], sp, win, mesh,
                                               k_scales=ks, v_scales=vs)
        else:
            out = paged_attention_span(q, kp, vp, page_table, q_pos[:, 0],
                                       sp, win, k_scales=ks, v_scales=vs)
        return out, new_cache
    # else: dense-gather fallback below (the engine counts the reason)

    MP = page_table.shape[1]
    KVh = kp.shape[2:]
    if quantized:
        # gather the int8 pages (quarter the fp32 bytes), then dequantize
        # the gathered blocks under their per-(page, head) scales
        from repro.core.quant import dequantize_kv_pages

        kk = dequantize_kv_pages(kp[page_table], ks[page_table]).astype(
            dtype).reshape(B, MP * pg, *KVh)
        vv = dequantize_kv_pages(vp[page_table], vs[page_table]).astype(
            dtype).reshape(B, MP * pg, *KVh)
    else:
        kk = kp[page_table].reshape(B, MP * pg, *KVh)  # (B,T,KV,hd)
        vv = vp[page_table].reshape(B, MP * pg, *KVh)
    kj = jnp.arange(MP * pg)[None, None, :]
    valid = kj <= q_pos[..., None]  # (B,S,T)
    if window is not None:
        valid &= (q_pos[..., None] - kj) < window
    mask = valid[:, None, None]  # (B,1,1,S,T)
    out = _sdpa(q, kk, vv, mask, cfg.logit_softcap, dtype,
                fast_scores=cfg.fast_decode_scores)
    return out, new_cache


# ---------------------------------------------------------------------------
# FFN variants
# ---------------------------------------------------------------------------


def ffn_init(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    spec = cfg.monarch
    gated = cfg.ffn_type in ("swiglu", "geglu")
    ks = jax.random.split(key, 3)
    w2 = linear_init(ks[1], ff, d, spec=spec,
                     w_init_scale=1.0 / math.sqrt(2 * max(cfg.n_layers, 1)))
    if gated and cfg.fused_proj:
        # up+gate in one weight visit; output layout [up, gate]
        return {"w1g": linear_init(ks[0], d, 2 * ff, spec=spec), "w2": w2}
    p = {"w1": linear_init(ks[0], d, ff, spec=spec), "w2": w2}
    if gated:
        p["wg"] = linear_init(ks[2], d, ff, spec=spec)
    return p


def ffn_apply(params: dict, x: jax.Array, cfg: ModelConfig,
              backend: str = "einsum") -> jax.Array:
    g = None
    if "w1g" in params:  # fused up+gate projection ([up, gate] layout)
        hg = linear_apply(params["w1g"], x, backend=backend)
        ff = hg.shape[-1] // 2
        h, g = hg[..., :ff], hg[..., ff:]
    else:
        h = linear_apply(params["w1"], x, backend=backend)
        if cfg.ffn_type in ("swiglu", "geglu"):
            g = linear_apply(params["wg"], x, backend=backend)
    if cfg.ffn_type == "swiglu":
        h = jax.nn.silu(g) * h
    elif cfg.ffn_type == "geglu":
        h = jax.nn.gelu(g) * h
    elif cfg.ffn_type == "gelu":
        h = jax.nn.gelu(h)
    elif cfg.ffn_type == "relu2":  # squared ReLU (nemotron / Primer)
        h = jnp.square(jax.nn.relu(h))
    else:
        raise ValueError(f"unknown ffn_type {cfg.ffn_type}")
    h = logical(h, "batch", "seq", "mlp")
    return linear_apply(params["w2"], h, backend=backend)


# ---------------------------------------------------------------------------
# Mixture of Experts (GShard-style capacity dispatch, shared + routed)
# ---------------------------------------------------------------------------


def moe_init(key, cfg: ModelConfig) -> dict:
    mc = cfg.moe
    de = mc.d_expert or cfg.d_ff
    ks = jax.random.split(key, 2 + mc.n_shared)
    # routed experts: stacked parameter trees (leading E axis) via vmap init;
    # the stack is padded to ``n_slots`` for even expert-parallel sharding,
    # padded slots are masked out of routing below.
    expert_keys = jax.random.split(ks[0], mc.n_slots)
    sub = dataclasses.replace(cfg, d_ff=de)
    experts = jax.vmap(lambda k: ffn_init(k, sub, d_ff=de))(expert_keys)
    p = {
        "router": linear_init(ks[1], cfg.d_model, mc.n_slots, spec=None),
        "experts": experts,
    }
    for i in range(mc.n_shared):
        p[f"shared{i}"] = ffn_init(ks[2 + i], sub, d_ff=de)
    return p


def moe_apply(
    params: dict, x: jax.Array, cfg: ModelConfig, backend: str = "einsum"
) -> tuple[jax.Array, dict]:
    """Grouped GShard dispatch: tokens are routed within fixed-size groups
    (capacity per group), keeping the dispatch tensors LINEAR in total
    tokens; groups shard over the data axes, experts over "model" (EP).
    Returns (output, aux) with the load-balance loss in aux."""
    mc: MoEConfig = cfg.moe
    B, S, d = x.shape
    T = B * S
    sg = min(mc.group_size, T)
    while T % sg:  # group size must tile the token count
        sg //= 2
    G = T // sg
    xt = x.reshape(G, sg, d)

    logits = linear_apply(params["router"], xt).astype(jnp.float32)  # (G,s,E)
    if mc.n_slots > mc.n_experts:  # mask EP-padding slots out of routing
        slot_ok = jnp.arange(mc.n_slots) < mc.n_experts
        logits = jnp.where(slot_ok[None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, mc.top_k)                  # (G,s,k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    E = mc.n_slots
    cap = max(4, int(mc.capacity_factor * sg * mc.top_k / mc.n_experts))

    dispatch = jnp.zeros((G, sg, E, cap), dtype=x.dtype)
    combine = jnp.zeros((G, sg, E, cap), dtype=jnp.float32)
    counts = jnp.zeros((G, E), dtype=jnp.int32)
    for k_slot in range(mc.top_k):  # slot priority, GShard-style
        sel = jax.nn.one_hot(idx[..., k_slot], E, dtype=jnp.int32)   # (G,s,E)
        pos = jnp.cumsum(sel, axis=1) - 1 + counts[:, None, :]
        counts = counts + jnp.sum(sel, axis=1)
        keep = (pos < cap) & (sel > 0)
        oh = jax.nn.one_hot(jnp.where(keep, pos, 0), cap, dtype=x.dtype)
        oh = oh * keep[..., None].astype(x.dtype)                    # (G,s,E,c)
        dispatch = dispatch + oh
        combine = combine + (
            oh.astype(jnp.float32)
            * gate_vals[..., k_slot, None, None]
            * sel[..., None].astype(jnp.float32)
        )

    dispatch = logical(dispatch, "expert_group", None, "expert", None)
    expert_in = jnp.einsum("gsec,gsd->egcd", dispatch, xt)
    expert_in = logical(expert_in, "expert", "expert_group", None, "embed")
    sub = dataclasses.replace(cfg, d_ff=mc.d_expert or cfg.d_ff)
    ein = expert_in.reshape(E, G * cap, d)
    expert_out = jax.vmap(lambda w, h: ffn_apply(w, h[None], sub, backend)[0])(
        params["experts"], ein
    ).reshape(E, G, cap, d)
    expert_out = logical(expert_out, "expert", "expert_group", None, "embed")
    out = jnp.einsum("gsec,egcd->gsd", combine.astype(x.dtype), expert_out)

    for i in range(mc.n_shared):
        out = out + ffn_apply(params[f"shared{i}"], xt, sub, backend)

    # load-balancing loss (Switch/GShard): E * sum_e f_e * p_e
    frac = jnp.mean(jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32),
                    axis=(0, 1))
    mean_prob = jnp.mean(probs, axis=(0, 1))
    aux = {"lb_loss": E * jnp.sum(frac * mean_prob)}
    return out.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embedding_init(key, cfg: ModelConfig) -> dict:
    std = 1.0 / math.sqrt(cfg.d_model)
    vp = cfg.vocab_padded  # padded so the vocab dim tiles the TP axis
    p = {"table": jax.random.normal(key, (vp, cfg.d_model)) * std}
    if not cfg.tie_embeddings:
        p["unembed"] = jax.random.normal(
            jax.random.fold_in(key, 1), (cfg.d_model, vp)
        ) * std
    return p


def embed(params: dict, tokens: jax.Array, cfg: ModelConfig, dtype) -> jax.Array:
    with jax.named_scope("embed"):
        x = params["table"][tokens].astype(dtype)  # gather rows, then cast
        x = x * math.sqrt(cfg.d_model) if cfg.norm_type == "rmsnorm" else x
        return logical(x, "batch", "seq", "embed")


def unembed(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    with jax.named_scope("unembed"):
        if cfg.tie_embeddings:
            w = params["table"].astype(x.dtype)
            logits = jnp.einsum("bsd,vd->bsv", x, w)
        else:
            w = params["unembed"].astype(x.dtype)
            logits = jnp.einsum("bsd,dv->bsv", x, w)
        logits = _softcap(logits.astype(jnp.float32), cfg.final_softcap)
        if cfg.vocab_padded > cfg.vocab:  # mask padding (softmax-neutral)
            valid = jnp.arange(cfg.vocab_padded) < cfg.vocab
            logits = jnp.where(valid[None, None, :], logits, -1e30)
        return logical(logits, "batch", "seq", "vocab")


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
    return jnp.mean(nll)


__all__ = [
    "norm_init", "norm_apply", "rope",
    "attention_init", "attention_apply", "attention_cache_init",
    "paged_cache_init", "causal_mask",
    "ffn_init", "ffn_apply", "moe_init", "moe_apply",
    "embedding_init", "embed", "unembed", "cross_entropy",
]
