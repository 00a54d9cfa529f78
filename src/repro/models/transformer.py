"""Model assembly: scanned decoder stacks, hybrid (Zamba) groups, enc-dec.

Layers are ``lax.scan``-stacked (stacked parameter pytrees + per-layer flag
arrays) so HLO size and compile time are depth-independent — essential for
the 512-device dry-run.  Per-layer attention windows are traced scalars
(local layers get ``cfg.window``, global layers a huge value), which lets
gemma2-style local/global alternation share one homogeneous scan.

Public API (all pure):
  init_params(key, cfg)                          -> params pytree
  forward(params, batch, cfg)                    -> (logits, aux)
  loss_fn(params, batch, cfg)                    -> (loss, aux)
  init_decode_cache(cfg, batch, max_len)         -> cache
  decode_step(params, tokens, cache, cfg)        -> (logits, cache)
  prefill(params, batch, cfg)                    -> last-position logits
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models import mamba2 as M
from repro.models.config import ModelConfig

GLOBAL_WINDOW = 1_000_000_000  # "no window": larger than any context


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# One decoder block (attention mixer + FFN/MoE)
# ---------------------------------------------------------------------------


def attn_block_init(key, cfg: ModelConfig, cross: bool = False) -> dict:
    ks = jax.random.split(key, 3)
    p = {
        "ln1": L.norm_init(cfg.d_model, cfg.norm_type),
        "attn": L.attention_init(ks[0], cfg),
        "ln2": L.norm_init(cfg.d_model, cfg.norm_type),
    }
    if cfg.moe is not None:
        p["moe"] = L.moe_init(ks[1], cfg)
    else:
        p["ffn"] = L.ffn_init(ks[1], cfg)
    if cfg.sandwich_norm:
        p["ln1_post"] = L.norm_init(cfg.d_model, cfg.norm_type)
        p["ln2_post"] = L.norm_init(cfg.d_model, cfg.norm_type)
    if cross:
        p["ln_x"] = L.norm_init(cfg.d_model, cfg.norm_type)
        # cross-attention k/v read enc_out, q reads the decoder stream —
        # never init it with a fused QKV projection
        xcfg = dataclasses.replace(cfg, fused_proj=False)
        p["xattn"] = L.attention_init(ks[2], xcfg)
    return p


def attn_block_apply(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    window=None,
    cache: Optional[dict] = None,
    pos=None,
    groups=None,
    enc_out=None,
    bidir: bool = False,
) -> tuple[jax.Array, Optional[dict], jax.Array]:
    """Returns (x, new_cache, lb_loss)."""
    h = L.norm_apply(p["ln1"], x, cfg.norm_type)
    a, new_attn_cache = L.attention_apply(
        p["attn"], h, cfg, window=window, cache=cache["attn"] if cache else None,
        pos=pos, groups=groups, bidir=bidir,
        backend=cfg.monarch.backend,
    )
    if cfg.sandwich_norm:
        a = L.norm_apply(p["ln1_post"], a, cfg.norm_type)
    x = x + a
    if "xattn" in p:
        h = L.norm_apply(p["ln_x"], x, cfg.norm_type)
        a, _ = L.attention_apply(
            p["xattn"], h, cfg, kv_input=enc_out, backend=cfg.monarch.backend
        )
        x = x + a
    h = L.norm_apply(p["ln2"], x, cfg.norm_type)
    lb = jnp.zeros((), jnp.float32)
    if cfg.moe is not None:
        f, moe_aux = L.moe_apply(p["moe"], h, cfg, backend=cfg.monarch.backend)
        lb = moe_aux["lb_loss"]
    else:
        f = L.ffn_apply(p["ffn"], h, cfg, backend=cfg.monarch.backend)
    if cfg.sandwich_norm:
        f = L.norm_apply(p["ln2_post"], f, cfg.norm_type)
    x = x + f
    new_cache = {"attn": new_attn_cache} if new_attn_cache is not None else None
    return x, new_cache, lb


# ---------------------------------------------------------------------------
# Stacks
# ---------------------------------------------------------------------------


def _layer_windows(cfg: ModelConfig) -> np.ndarray:
    return np.asarray(
        [cfg.window if cfg.attn_kind(i) == "local" else GLOBAL_WINDOW
         for i in range(cfg.n_layers)],
        dtype=np.int32,
    )


def _maybe_remat(fn, cfg: ModelConfig, train: bool):
    if not train:
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    return fn


def _mamba_layer(p, x, cfg, cache):
    h = L.norm_apply(p["ln"], x, cfg.norm_type)
    y, new_cache = M.mamba_apply(p["mamba"], h, cfg, cache=cache,
                                 backend=cfg.monarch.backend)
    return x + y, new_cache


def _mamba_layer_init(key, cfg):
    return {"ln": L.norm_init(cfg.d_model, cfg.norm_type),
            "mamba": M.mamba_init(key, cfg)}


def decoder_stack_init(key, cfg: ModelConfig, cross: bool = False) -> dict:
    if cfg.layer_kind == "attn":
        keys = jax.random.split(key, cfg.n_layers)
        return {"layers": jax.vmap(
            lambda k: attn_block_init(k, cfg, cross=cross))(keys)}
    if cfg.layer_kind == "mamba":
        keys = jax.random.split(key, cfg.n_layers)
        return {"layers": jax.vmap(lambda k: _mamba_layer_init(k, cfg))(keys)}
    # hybrid (Zamba2): groups of `shared_attn_every` mamba layers + one
    # shared-weight attention block; leftover layers become a tail scan.
    g = cfg.shared_attn_every
    n_groups = cfg.n_layers // g
    tail = cfg.n_layers - n_groups * g
    kg, kt, ka = jax.random.split(key, 3)
    gkeys = jax.random.split(kg, n_groups * g).reshape(n_groups, g, -1)
    grouped = jax.vmap(jax.vmap(lambda k: _mamba_layer_init(k, cfg)))(gkeys)
    p = {"groups": grouped, "shared_attn": attn_block_init(ka, cfg)}
    if tail:
        p["tail"] = jax.vmap(lambda k: _mamba_layer_init(k, cfg))(
            jax.random.split(kt, tail))
    return p


def decoder_stack_apply(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    cache: Optional[dict] = None,
    pos=None,
    groups=None,
    enc_out=None,
    bidir: bool = False,
    train: bool = True,
) -> tuple[jax.Array, Optional[dict], dict]:
    aux = {"lb_loss": jnp.zeros((), jnp.float32)}

    if cfg.layer_kind == "attn":
        windows = jnp.asarray(_layer_windows(cfg))
        if cache is None:
            def body(h, pl):
                p, win = pl
                h, _, lb = attn_block_apply(
                    p, h, cfg, window=win, enc_out=enc_out, bidir=bidir)
                return h, lb
            body = _maybe_remat(body, cfg, train)
            x, lbs = jax.lax.scan(body, x, (params["layers"], windows))
            aux["lb_loss"] = jnp.sum(lbs) / cfg.n_layers
            return x, None, aux

        def body(h, pl):
            p, win, c = pl
            h, nc, lb = attn_block_apply(
                p, h, cfg, window=win, cache=c, pos=pos, groups=groups,
                enc_out=enc_out)
            return h, (nc, lb)
        x, (new_caches, lbs) = jax.lax.scan(
            body, x, (params["layers"], windows, cache["layers"]))
        aux["lb_loss"] = jnp.sum(lbs) / cfg.n_layers
        return x, {"layers": new_caches}, aux

    if cfg.layer_kind == "mamba":
        if cache is None:
            def body(h, p):
                h, _ = _mamba_layer(p, h, cfg, None)
                return h, None
            body = _maybe_remat(body, cfg, train)
            x, _ = jax.lax.scan(body, x, params["layers"])
            return x, None, aux

        def body(h, pl):
            p, c = pl
            h, nc = _mamba_layer(p, h, cfg, c)
            return h, nc
        x, new_caches = jax.lax.scan(body, x, (params["layers"], cache["layers"]))
        return x, {"layers": new_caches}, aux

    # hybrid
    g = cfg.shared_attn_every
    n_groups = cfg.n_layers // g
    shared = params["shared_attn"]

    if cache is None:
        def group_body(h, gp):
            def inner(hh, p):
                hh, _ = _mamba_layer(p, hh, cfg, None)
                return hh, None
            h, _ = jax.lax.scan(inner, h, gp)
            h, _, _ = attn_block_apply(shared, h, cfg, window=None)
            return h, None
        group_body = _maybe_remat(group_body, cfg, train)
        x, _ = jax.lax.scan(group_body, x, params["groups"])
        new_cache = None
        if "tail" in params:
            def tail_body(h, p):
                h, _ = _mamba_layer(p, h, cfg, None)
                return h, None
            x, _ = jax.lax.scan(tail_body, x, params["tail"])
        return x, new_cache, aux

    def group_body(h, pl):
        gp, gc = pl
        def inner(hh, pl2):
            p, c = pl2
            hh, nc = _mamba_layer(p, hh, cfg, c)
            return hh, nc
        h, new_m = jax.lax.scan(inner, h, (gp, gc["mamba"]))
        h, new_a, _ = attn_block_apply(
            shared, h, cfg, window=None, cache={"attn": gc["attn"]}, pos=pos)
        return h, {"mamba": new_m, "attn": new_a["attn"]}
    x, new_groups = jax.lax.scan(
        group_body, x, (params["groups"], cache["groups"]))
    new_cache = {"groups": new_groups}
    if "tail" in params:
        def tail_body(h, pl):
            p, c = pl
            h, nc = _mamba_layer(p, h, cfg, c)
            return h, nc
        x, new_tail = jax.lax.scan(tail_body, x, (params["tail"], cache["tail"]))
        new_cache["tail"] = new_tail
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Whole models
# ---------------------------------------------------------------------------


def init_params(key, cfg: ModelConfig) -> dict:
    k_emb, k_dec, k_enc = jax.random.split(key, 3)
    p = {
        "embedding": L.embedding_init(k_emb, cfg),
        "decoder": decoder_stack_init(k_dec, cfg, cross=cfg.encdec),
        "ln_f": L.norm_init(cfg.d_model, cfg.norm_type),
    }
    if cfg.encdec:
        enc_cfg = dataclasses.replace(
            cfg, n_layers=cfg.n_enc_layers, moe=None, layer_kind="attn")
        p["encoder"] = decoder_stack_init(k_enc, enc_cfg)
        p["ln_enc"] = L.norm_init(cfg.d_model, cfg.norm_type)
    return p


def serving_params(params, cfg: ModelConfig) -> dict:
    """Floating weights cast to the activation dtype (``cfg.dtype``): how
    the serving engine stores them.  A bf16 config keeps 2-byte weights on
    device; an fp32 config comes back unchanged.  Per-block quantization
    scales (``Ls``/``Rs``) keep their fp32 width."""
    dt = _dtype(cfg)

    def cast(path, a):
        if (getattr(path[-1], "key", None) in ("Ls", "Rs")
                or not jnp.issubdtype(a.dtype, jnp.floating)):
            return a
        return a.astype(dt)

    return jax.tree_util.tree_map_with_path(cast, params)


def _encode(params, batch, cfg: ModelConfig, train: bool):
    """Encoder pass (bidirectional).  The audio frontend is a stub: the
    batch carries precomputed frame embeddings (DESIGN.md Sec. 6)."""
    enc_cfg = dataclasses.replace(
        cfg, n_layers=cfg.n_enc_layers, moe=None, layer_kind="attn")
    if "enc_embeds" in batch:
        h = batch["enc_embeds"].astype(_dtype(cfg))
    else:
        h = L.embed(params["embedding"], batch["enc_tokens"], cfg, _dtype(cfg))
    h, _, _ = decoder_stack_apply(params["encoder"], h, enc_cfg, bidir=True,
                                  train=train)
    return L.norm_apply(params["ln_enc"], h, cfg.norm_type)


def forward(params, batch: dict, cfg: ModelConfig, train: bool = True):
    dtype = _dtype(cfg)
    x = L.embed(params["embedding"], batch["tokens"], cfg, dtype)
    n_front = 0
    if cfg.frontend == "vision" and "patch_embeds" in batch:
        # VLM stub: precomputed patch embeddings prepended to the text tokens
        n_front = batch["patch_embeds"].shape[1]
        x = jnp.concatenate([batch["patch_embeds"].astype(dtype), x], axis=1)
    enc_out = _encode(params, batch, cfg, train) if cfg.encdec else None
    x, _, aux = decoder_stack_apply(
        params["decoder"], x, cfg, enc_out=enc_out, train=train)
    if n_front:
        x = x[:, n_front:, :]
    x = L.norm_apply(params["ln_f"], x, cfg.norm_type)
    logits = L.unembed(params["embedding"], x, cfg)
    return logits, aux


def loss_fn(params, batch: dict, cfg: ModelConfig):
    logits, aux = forward(params, batch, cfg, train=True)
    loss = L.cross_entropy(logits, batch["labels"], batch.get("loss_mask"))
    if cfg.moe is not None:
        loss = loss + 0.01 * aux["lb_loss"]
    return loss, aux


# ---- serving -------------------------------------------------------------


def _bcast(tree, prefix: tuple):
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, prefix + x.shape) + 0, tree)


def init_decode_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    dtype = _dtype(cfg)
    if cfg.layer_kind == "attn":
        one = {"attn": L.attention_cache_init(cfg, batch, max_len, dtype)}
        cache = {"layers": _bcast(one, (cfg.n_layers,))}
    elif cfg.layer_kind == "mamba":
        cache = {"layers": _bcast(M.mamba_cache_init(cfg, batch, dtype),
                                  (cfg.n_layers,))}
    else:
        g = cfg.shared_attn_every
        n_groups = cfg.n_layers // g
        tail = cfg.n_layers - n_groups * g
        cache = {"groups": {
            "mamba": _bcast(M.mamba_cache_init(cfg, batch, dtype), (n_groups, g)),
            "attn": _bcast(L.attention_cache_init(cfg, batch, max_len, dtype),
                           (n_groups,)),
        }}
        if tail:
            cache["tail"] = _bcast(M.mamba_cache_init(cfg, batch, dtype), (tail,))
    cache["pos"] = jnp.zeros((batch,), jnp.int32)
    return cache


def decode_step(params, tokens: jax.Array, cache: dict, cfg: ModelConfig,
                enc_out=None):
    """One new token per batch row against the running cache."""
    dtype = _dtype(cfg)
    pos = cache["pos"]
    x = L.embed(params["embedding"], tokens[:, None], cfg, dtype)
    inner = {k: v for k, v in cache.items() if k != "pos"}
    x, new_inner, _ = decoder_stack_apply(
        params["decoder"], x, cfg, cache=inner, pos=pos, enc_out=enc_out,
        train=False)
    x = L.norm_apply(params["ln_f"], x, cfg.norm_type)
    logits = L.unembed(params["embedding"], x, cfg)
    new_cache = dict(new_inner or {})
    new_cache["pos"] = pos + 1
    return logits[:, 0], new_cache


def prefill(params, batch: dict, cfg: ModelConfig):
    logits, _ = forward(params, batch, cfg, train=False)
    return logits[:, -1]


def prefill_with_cache(params, tokens: jax.Array, cache: dict, cfg: ModelConfig):
    """Batched prompt prefill through the ring cache: ONE forward over the
    (B, S) prompt block writes all S k/v rows per layer, replacing the seed
    engine's S sequential ``decode_step`` calls.  Attn stacks only (SSM
    states advance one token at a time).  Returns (last-position logits,
    updated cache)."""
    assert cfg.layer_kind == "attn", "batched cache prefill needs attn layers"
    B, S = tokens.shape
    dtype = _dtype(cfg)
    pos = cache["pos"]
    x = L.embed(params["embedding"], tokens, cfg, dtype)
    inner = {k: v for k, v in cache.items() if k != "pos"}
    x, new_inner, _ = decoder_stack_apply(
        params["decoder"], x, cfg, cache=inner, pos=pos, train=False)
    x = L.norm_apply(params["ln_f"], x, cfg.norm_type)
    logits = L.unembed(params["embedding"], x[:, -1:], cfg)
    new_cache = dict(new_inner or {})
    new_cache["pos"] = pos + S
    return logits[:, 0], new_cache


# ---- paged serving (continuous batching) ----------------------------------


def init_paged_pool(cfg: ModelConfig, n_pages: int, page_size: int,
                    kv_dtype: Optional[str] = None) -> dict:
    """Paged KV pool for the whole stack: per-layer page arrays, stacked on a
    leading layer axis so the scanned decoder threads them like any cache.
    Page 0 is the sink page — free slots' page tables point at it.

    ``kv_dtype`` ("fp32" | "bf16" | "int8" | None = model dtype) selects the
    stored page width; "int8" adds per-layer (P, KV) fp32 scale buffers
    (one scale per (page, head), K and V independent — ``core.quant``)."""
    assert cfg.layer_kind == "attn", "paged KV cache needs attn layers"
    dtype = _dtype(cfg)
    one = {"attn": L.paged_cache_init(cfg, n_pages, page_size, dtype,
                                      kv_dtype=kv_dtype)}
    return {"layers": _bcast(one, (cfg.n_layers,))}


def cow_copy_pages(pool: dict, src: jax.Array, dst: jax.Array) -> dict:
    """Device half of a copy-on-write fork: copy whole pages ``src[i]`` ->
    ``dst[i]`` in every layer's k/v page arrays ((L, P, page, KV, hd) —
    the page axis is axis 1).  A quantized pool's per-(page, head) scale
    buffers ((L, P, KV) — same page axis) ride the same tree_map, so a COW
    fork copies page bytes and scales together and stays exact: the fork
    dequantizes to the very values the source held.

    Whole-page copies are sufficient even when only the first ``n`` rows of
    the source are logically shared: rows past the fork point are the source
    sequence's own continuation, which the forking sequence's causal mask
    hides until its span writes (positions >= the fork point, enforced by
    ``write_start``) overwrite them.  Entries may repeat the sink page as
    padding (sink copied onto itself is a no-op by value)."""
    return jax.tree_util.tree_map(
        lambda a: a.at[:, dst].set(a[:, src]), pool)


def paged_mixed_step(params, tokens: jax.Array, start: jax.Array,
                     span_len: jax.Array, page_table: jax.Array, pool: dict,
                     cfg: ModelConfig, write_start: jax.Array = None,
                     decode: Optional[L.SpanRows] = None):
    """ONE unified engine iteration: each row contributes a variable-length
    token span — a prefill chunk, the tail of a chunked prompt, or a single
    decode token — laid out as up to two row groups packed into one token
    set.

    The span group: tokens (R, S) right-padded spans; row ``b``'s token
    ``i`` sits at global position ``start[b] + i`` and is real iff
    ``i < span_len[b]``.  ``decode``, when given, is a second group of
    one position per row (tokens (B, 1)), which the step carries ahead of
    the span group.  Norms, linears and the FFN run once over the B + R x S
    packed positions; attention splits them back into the groups.

    Real positions write k/v through their group's ``page_table`` into the
    shared pool; padding positions are redirected to the sink page (they
    can never touch a live page — with incremental allocation the table
    may not even cover them).  ``write_start`` (rows,), when given, is each
    row's copy-on-write fork point: positions below it live in
    refcount-shared prefix pages and are additionally redirected to the
    sink — span writes are provably confined to pages the row exclusively
    owns, whatever the host hands in.  (Reads are unaffected: attention
    gathers shared pages through the page table like any other.)  Attention
    is causal within the span and over all previously written positions.
    A span of 0 makes the row fully inert (pool untouched, logits garbage —
    the engine only samples rows whose span reaches the end of their known
    tokens).  A group with no rows is left out.

    Returns (logits at each row's last real position — the decode group's
    rows first, then the span group's — and the updated pool).  Prefill is
    span == prompt chunk, decode is span == 1.
    """
    dtype = _dtype(cfg)
    span = L.SpanRows(tokens, start, span_len, page_table, write_start)
    groups = tuple(g for g in (decode, span)
                   if g is not None and g.tokens.size)
    packed = jnp.concatenate([g.tokens.reshape(-1) for g in groups])[None]
    x = L.embed(params["embedding"], packed, cfg, dtype)  # (1, N, d)
    x, new_pool, _ = decoder_stack_apply(
        params["decoder"], x, cfg, cache=pool, groups=groups, train=False)
    x = L.norm_apply(params["ln_f"], x, cfg.norm_type)
    last, off = [], 0
    for g in groups:
        n, s = g.tokens.shape
        xg = x[0, off:off + n * s].reshape(n, s, -1)
        if s > 1:   # last real position of each row
            idx = (jnp.maximum(g.span_len, 1) - 1)[:, None, None]
            xg = jnp.take_along_axis(xg, idx, axis=1)
        last.append(xg[:, :1])
        off += n * s
    xl = jnp.concatenate(last)  # (rows, 1, d)
    logits = L.unembed(params["embedding"], xl, cfg)
    # under a tensor-parallel trace the unembed leaves logits split on the
    # vocab axis; constrain them so sampling sees the full row (no-op when
    # no mesh is active or vocab doesn't divide the model axis)
    from repro.sharding import logical
    logits = logical(logits, "batch", "seq", None)
    return logits[:, 0], new_pool


__all__ = [
    "init_params", "serving_params", "forward", "loss_fn",
    "init_decode_cache", "decode_step", "prefill", "prefill_with_cache",
    "init_paged_pool", "paged_mixed_step", "cow_copy_pages",
    "decoder_stack_init", "decoder_stack_apply",
    "attn_block_init", "attn_block_apply",
]
