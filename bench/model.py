"""A configuration file -> the program's ``ModelConfig``, and its weights.

The configuration file (``bench/configs/<name>.json``) is authoritative: its
``model`` sizes replace the fields of the registry entry it names
(``arch``), so a later change to ``src/repro/configs`` does not move the
benchmark.  The weights are made here, on the device, in one jitted call
from the seed, directly in the served dtype.  They are data: the engine and
the plain reference (``bench/reference.py``) both read this tree, and
neither made it.
"""

from __future__ import annotations

import dataclasses
import math
import typing

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def model_config(spec: dict):
    """``ModelConfig`` as the file states it: the registry entry ``arch``
    with every key of ``model`` replaced (nested settings field by field)."""
    from repro.configs import get_config

    return _replaced(get_config(spec["arch"]), spec["model"])


def _replaced(obj, fields: dict):
    """The dataclass ``obj`` with ``fields`` replaced.  A dict given for a
    dataclass-valued field (``monarch``, ``moe``, ``ssm``) builds it field
    by field, from the field's value or, where that is None, from its type;
    a list given for a tuple field becomes a tuple."""
    hints = typing.get_type_hints(type(obj))
    return dataclasses.replace(obj, **{
        name: _value(hints[name], getattr(obj, name), value)
        for name, value in fields.items()})


def _value(hint, current, value):
    """``value`` as the field of type ``hint`` (now ``current``) takes it."""
    kinds = [t for t in typing.get_args(hint) if t is not type(None)]
    kind = kinds[0] if typing.get_origin(hint) is typing.Union else hint
    if isinstance(value, dict) and dataclasses.is_dataclass(kind):
        if current is None:
            hints = typing.get_type_hints(kind)
            return kind(**{k: _value(hints[k], None, v)
                           for k, v in value.items()})
        return _replaced(current, value)
    if isinstance(value, list) and (kind is tuple
                                    or typing.get_origin(kind) is tuple):
        return tuple(value)
    return value


# ---------------------------------------------------------------------------
# Monarch shapes (paper policy), copied so the yardstick does not move
# ---------------------------------------------------------------------------


def closest_divisor(n: int, target: int) -> int:
    best, best_dist = 1, abs(1 - target)
    for d in range(1, math.isqrt(n) + 1):
        if n % d == 0:
            for cand in (d, n // d):
                dist = abs(cand - target)
                if dist < best_dist or (dist == best_dist and cand < best):
                    best, best_dist = cand, dist
    return best


def monarch_blocks(din: int, dout: int) -> tuple[int, int, int, int]:
    """(k, q, p, s) of the paper policy: ``k`` blocks of ``p`` inputs map
    to ``q`` outputs each, then ``q`` blocks of ``k`` map to ``s``."""
    b = closest_divisor(din, int(round(math.sqrt(din))))
    k = din // b
    q = k if dout % k == 0 else closest_divisor(dout, k)
    return k, q, din // k, dout // q


def is_factorized(cfg, din: int, dout: int) -> bool:
    m = cfg.monarch
    if m.policy != "paper" or m.nblocks is not None:
        raise ValueError("the benchmark's weights follow the paper policy only")
    return m.enable and min(din, dout) >= m.min_dim


def linears(cfg) -> dict:
    """Every parameterized matmul of one decoder layer: name -> (din, dout,
    output scale of the init)."""
    d, h, kv, hd, ff = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                        cfg.d_ff)
    out_scale = 1.0 / math.sqrt(2 * max(cfg.n_layers, 1))
    attn = {"wq": (d, h * hd, 1.0), "wk": (d, kv * hd, 1.0),
            "wv": (d, kv * hd, 1.0), "wo": (h * hd, d, out_scale)}
    ffn = {"w1": (d, ff, 1.0), "w2": (ff, d, out_scale)}
    if cfg.ffn_type in ("swiglu", "geglu"):
        ffn["wg"] = (d, ff, 1.0)
    return {"attn": attn, "ffn": ffn}


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole number (more than 32 bits hold)."""
    words = np.random.SeedSequence(int(seed) % 2**64).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _weight_tree(key, cfg):
    """The served weight tree, drawn with the program's init variances:
    Monarch factors var(L) = 1/p, var(R) = scale^2/k; dense and embedding
    std 1/sqrt(d_in); norm scales 1 and biases 0."""
    dt = DTYPES[cfg.dtype]
    n = cfg.n_layers
    counter = iter(range(1 << 20))

    def normal(shape, std):
        k = jax.random.fold_in(key, next(counter))
        return jax.random.normal(k, shape, dt) * jnp.asarray(std, dt)

    def norm(stacked: bool):
        shape = (n, cfg.d_model) if stacked else (cfg.d_model,)
        p = {"scale": jnp.ones(shape, dt)}
        if cfg.norm_type != "rmsnorm":
            p["bias"] = jnp.zeros(shape, dt)
        return p

    def linear(din, dout, scale):
        if is_factorized(cfg, din, dout):
            k, q, p, s = monarch_blocks(din, dout)
            return {"L": normal((n, k, q, p), math.sqrt(1.0 / p)),
                    "R": normal((n, q, s, k), scale * math.sqrt(1.0 / k))}
        return {"w": normal((n, din, dout), scale / math.sqrt(din))}

    layer = {"ln1": norm(True), "ln2": norm(True)}
    for group, mats in linears(cfg).items():
        layer[group] = {name: linear(*dims) for name, dims in mats.items()}
    std = 1.0 / math.sqrt(cfg.d_model)
    emb = {"table": normal((cfg.vocab_padded, cfg.d_model), std)}
    if not cfg.tie_embeddings:
        emb["unembed"] = normal((cfg.d_model, cfg.vocab_padded), std)
    return {"embedding": emb, "decoder": {"layers": layer},
            "ln_f": norm(False)}


def make_weights(cfg, seed: int, tree=None):
    """All weights on the default device, one jitted call, served dtype:
    ``tree(key, cfg)``, a family's ``weight_tree`` (this module's dense
    decoder by default)."""
    tree = tree or _weight_tree
    check_layout(cfg, tree)
    return jax.jit(tree, static_argnums=1)(seed_key(seed), cfg)


def check_layout(cfg, tree) -> None:
    """Fail loudly when the program's served weight layout is not the one
    ``tree`` draws (a changed key, shape or dtype)."""
    from repro.models import transformer as T

    want = jax.eval_shape(
        lambda k: T.serving_params(T.init_params(k, cfg), cfg),
        jax.random.PRNGKey(0))
    have = jax.eval_shape(lambda k: tree(k, cfg), seed_key(0))
    sig = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: (a.shape, str(a.dtype)), t)
    if sig(want) != sig(have):
        raise RuntimeError("the program's served weight layout differs from "
                           f"the benchmark's weight maker ({tree.__module__})")


def weight_bytes(tree) -> int:
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree))
