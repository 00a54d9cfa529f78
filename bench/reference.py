"""Plain float32 reference of the served decoder.

Straightforward ``jax.numpy``: no kernels, no KV cache, no batching, one
sequence at a time, one layer at a time, every matmul at
``jax.default_matmul_precision("highest")``.  It imports nothing of the
program.  It reads the weight tree that ``bench/model.py`` drew (weights
are data) and the sizes of the configuration file.

It follows the program where the program departs from the published
models (listed in PERF.md): RMSNorm multiplies by ``1 + scale`` and the
embedding of an RMSNorm model is scaled by ``sqrt(d_model)``; norms use
eps 1e-6; rotary embedding rotates the two halves of each head.

Monarch linears are computed from their two block-diagonal factors:
``x`` (k blocks of p) -> ``u[k, q] = sum_p L[k, q, p] x[k, p]`` -> the
stride permutation (index ``k`` becomes the inner one) ->
``y[q, s] = sum_k R[q, s, k] u[k, q]``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6
# vocabulary columns of the unembedding upcast at a time
VOCAB_BLOCK = 32768


def _linear(w: dict, x):
    x = x.astype(jnp.float32)
    if "w" in w:
        return x @ w["w"].astype(jnp.float32)
    L, R = w["L"].astype(jnp.float32), w["R"].astype(jnp.float32)
    k, q, p = L.shape
    _, s, _ = R.shape
    u = jnp.einsum("tkp,kqp->tkq", x.reshape(x.shape[0], k, p), L)
    return jnp.einsum("tkq,qsk->tqs", u, R).reshape(x.shape[0], q * s)


def _norm(w: dict, x, kind: str):
    if kind == "rmsnorm":
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x / jnp.sqrt(var + EPS) * (1.0 + w["scale"].astype(jnp.float32))
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return ((x - mean) / jnp.sqrt(var + EPS) * w["scale"].astype(jnp.float32)
            + w["bias"].astype(jnp.float32))


def _rope(x, theta: float):
    """x: (T, heads, hd), position t = row index."""
    T, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / theta ** (np.arange(half, dtype=np.float64) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("m",))
def _layer(x, layers, i, m):
    """Decoder layer ``i`` over a whole sequence x: (T, d) float32."""
    w = jax.tree_util.tree_map(lambda a: a[i], layers)
    T = x.shape[0]
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    h = _norm(w["ln1"], x, m["norm_type"])
    q = _rope(_linear(w["attn"]["wq"], h).reshape(T, H, hd), m["rope_theta"])
    k = _rope(_linear(w["attn"]["wk"], h).reshape(T, KV, hd), m["rope_theta"])
    v = _linear(w["attn"]["wv"], h).reshape(T, KV, hd)
    g = H // KV
    s = jnp.einsum("tngh,unh->ngtu", q.reshape(T, KV, g, hd), k)
    s = s / math.sqrt(hd)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("ngtu,unh->tngh", p, v).reshape(T, H * hd)
    x = x + _linear(w["attn"]["wo"], a)
    h = _norm(w["ln2"], x, m["norm_type"])
    f = w["ffn"]
    if m["ffn_type"] == "swiglu":
        u = jax.nn.silu(_linear(f["wg"], h)) * _linear(f["w1"], h)
    elif m["ffn_type"] == "relu2":
        u = jnp.square(jnp.maximum(_linear(f["w1"], h), 0.0))
    else:
        raise ValueError(f"reference has no FFN {m['ffn_type']!r}")
    return x + _linear(f["w2"], u)


@functools.partial(jax.jit, static_argnames=("m",))
def _embed(table, tokens, m):
    x = table[tokens].astype(jnp.float32)
    if m["norm_type"] == "rmsnorm":
        x = x * math.sqrt(m["d_model"])
    return x


@functools.partial(jax.jit, static_argnames=("m",))
def _final(x, rows, w_ln, m):
    return _norm(w_ln, x[rows], m["norm_type"])


@functools.partial(jax.jit, static_argnames=("block",))
def _logit_block(h, unembed, served, start, vocab, block):
    """Columns [start, start + block) of the logits, reduced on the device:
    each row's best logit and its column, and the served token's logit
    (valid where ``inside``).  Padding columns (>= vocab) never win."""
    w = jax.lax.dynamic_slice_in_dim(unembed, start, block, axis=1)
    lg = h @ w.astype(jnp.float32)
    cols = start + jnp.arange(block)
    lg = jnp.where(cols[None, :] < vocab, lg, -jnp.inf)
    inside = (served >= start) & (served < start + block)
    at = jnp.clip(served - start, 0, block - 1)
    picked = jnp.take_along_axis(lg, at[:, None], axis=1)[:, 0]
    return lg.max(axis=1), start + lg.argmax(axis=1), picked, inside


class _Static(dict):
    """A hashable view of the model sizes (a static jit argument)."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _static(model: dict) -> _Static:
    unsupported = {"moe", "logit_softcap", "final_softcap", "qk_norm",
                   "sandwich_norm", "fused_proj", "attn_pattern"}
    bad = unsupported & set(model)
    if bad or model.get("tie_embeddings", True) is not False:
        raise ValueError(f"reference does not cover {sorted(bad) or 'tied embeddings'}")
    keys = ("d_model", "n_heads", "n_kv_heads", "head_dim", "norm_type",
            "ffn_type", "rope_theta", "n_layers", "vocab")
    return _Static({k: model[k] for k in keys})


def score(model: dict, weights, prompt, served, length: int) -> dict:
    """Reference logits at the position of every served token of one
    request: ``prompt`` then ``served`` (the tokens the program emitted),
    padded to ``length`` positions (the mix's ``max_len``: one program for
    every request; padding follows every real position and causality hides
    it).  Returns, per served token, the reference's best logit, the logit
    of the served token, and the reference's argmax."""
    m = _static(model)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    T = len(seq)
    if T > length:
        raise ValueError(f"{T} positions exceed the padded length {length}")
    tokens = np.zeros((length,), np.int32)
    tokens[:T] = seq
    # rows of the served tokens, padded to a fixed count (one program)
    n = len(served)
    rows = np.zeros((length,), np.int32)
    rows[:n] = np.arange(len(prompt) - 1, T, dtype=np.int32)
    want = np.zeros((length,), np.int32)
    want[:n] = served
    layers = weights["decoder"]["layers"]
    un = weights["embedding"]["unembed"]
    block = min(VOCAB_BLOCK, un.shape[1])
    best = np.full(n, -np.inf)
    arg = np.zeros(n, np.int64)
    picked = np.zeros(n)
    with jax.default_matmul_precision("highest"):
        x = _embed(weights["embedding"]["table"], jnp.asarray(tokens), m)
        for i in range(m["n_layers"]):
            x = _layer(x, layers, jnp.int32(i), m)
        h = _final(x, jnp.asarray(rows), weights["ln_f"], m)
        for c0 in range(0, m["vocab"], block):
            start = min(c0, un.shape[1] - block)  # the last block overlaps
            top, top_at, got, inside = (np.asarray(a)[:n] for a in _logit_block(
                h, un, jnp.asarray(want), jnp.int32(start),
                jnp.int32(m["vocab"]), block))
            arg = np.where(top > best, top_at, arg)
            best = np.maximum(best, top)
            picked = np.where(inside, got, picked)
    return dict(best=best, served=picked, argmax=arg)
