"""Size the page pool from the compiled step's memory analysis.

The engine's mixed step compiles once per span bucket (powers of two up to
the scheduler's ``chunk_size``).  Its footprint is the weights, the pool
(twice: the layer scan writes a new pool beside the old one) and the
temporaries of the bucket.  The largest bucket is compiled ahead of time at
two small pool sizes (a sixteenth and an eighth of every slot at
``max_len``); the footprint is linear in the page count, so the largest
pool that keeps the step under ``MEMORY_SHARE`` of the device is read off
the line and then compiled once more to confirm (the chip's compiler
refuses a program that does not fit).  Nothing is hard-coded: a
change to the program that frees device memory gets more pages here.

The result is kept in ``.bench_cache/`` in the checkout, keyed by the
program's source, the configuration, the mix and the device, so only the
first run of a checkout pays the three compiles.
"""

from __future__ import annotations

import hashlib
import json
import math
import os
from pathlib import Path

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".bench_cache"
# share of the device's memory one compiled step may take; the rest is for
# the runtime, the sampled tokens and the small page-table programs
MEMORY_SHARE = 0.9


def span_buckets(chunk_size: int) -> list[int]:
    """Every span bucket a step can dispatch: powers of two up to the
    bucket of ``chunk_size`` (the engine pads a step's longest span)."""
    top = 1 << (chunk_size - 1).bit_length()
    return [1 << i for i in range(top.bit_length())]


def step_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def compile_step(cfg, weights, n_pages, slots, page_size, max_len, span):
    """AOT-compile the engine's mixed step for one span bucket."""
    from repro.models import transformer as T
    from repro.serving.engine import _mixed_step_jit

    pool = jax.eval_shape(lambda: T.init_paged_pool(cfg, n_pages, page_size))
    mp = math.ceil(max_len / page_size)
    i32 = jnp.int32
    rows = [((slots, span), i32), ((slots,), i32), ((slots,), bool),
            ((slots,), i32), ((slots,), i32), ((slots, mp), i32),
            ((slots,), i32), ((slots,), bool), ((slots,), jnp.float32),
            ((slots, 2), jnp.uint32)]
    args = [jax.ShapeDtypeStruct(s, d) for s, d in rows]
    shapes = jax.eval_shape(lambda w: w, weights)
    return _mixed_step_jit.lower(shapes, pool, *args, cfg=cfg).compile()


def _bytes_or_none(cfg, weights, n_pages, slots, page_size, max_len, span):
    """The step's footprint at ``n_pages``, or None where the chip's
    compiler refuses it for want of memory."""
    try:
        return step_bytes(compile_step(cfg, weights, n_pages, slots,
                                       page_size, max_len, span))
    except jax.errors.JaxRuntimeError as e:
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        return None


def _key(cfg, slots, page_size, max_len, span, limit) -> str:
    h = hashlib.sha256()
    src = ROOT / "src"
    for p in sorted(src.rglob("*.py")):
        h.update(str(p.relative_to(src)).encode())
        h.update(p.read_bytes())
    dev = jax.devices()[0]
    h.update(repr((cfg, slots, page_size, max_len, span, limit,
                   dev.device_kind, jax.__version__)).encode())
    return h.hexdigest()[:24]


def size_pool(cfg, weights, slots: int, page_size: int, max_len: int,
              chunk_size: int, log=print) -> dict:
    """Largest page count (at most every slot at ``max_len``) whose largest
    span bucket fits ``MEMORY_SHARE`` of the device."""
    full = 1 + slots * math.ceil(max_len / page_size)
    stats = jax.devices()[0].memory_stats()
    if stats is None:      # a backend that reports no memory: the CPU tests
        return dict(n_pages=full, step_bytes=0, limit_bytes=0,
                    bytes_per_page=0.0, span=0)
    limit = stats["bytes_limit"]
    span = span_buckets(chunk_size)[-1]
    key = _key(cfg, slots, page_size, max_len, span, limit)
    path = CACHE_DIR / f"pool-{key}.json"
    if path.exists():
        return json.loads(path.read_text())
    budget = MEMORY_SHARE * limit
    # two pool sizes small enough to compile, then the line through them
    lo, hi = max(2, full // 16), max(3, full // 8)
    b_lo = _bytes_or_none(cfg, weights, lo, slots, page_size, max_len, span)
    b_hi = _bytes_or_none(cfg, weights, hi, slots, page_size, max_len, span)
    if b_lo is None or b_hi is None:
        raise RuntimeError("the mixed step does not fit the device even "
                           f"with a pool of {lo} pages")
    per_page = (b_hi - b_lo) / (hi - lo)
    n = min(full, int((budget - b_lo) / per_page) + lo)
    while True:
        if n < lo:
            raise RuntimeError("the mixed step does not fit the device")
        total = _bytes_or_none(cfg, weights, n, slots, page_size, max_len,
                               span)
        if total is not None and total <= budget:
            break
        n = int(n * 0.97)
    # Forget what these compiles traced.  The engine would reuse the traced
    # pieces, whose op metadata points here, and the persistent cache,
    # keyed with that metadata, would hold programs that the next run,
    # which reads the pool size from CACHE_DIR, never builds: it would
    # compile them anew.
    jax.clear_caches()
    res = dict(n_pages=n, step_bytes=total, limit_bytes=limit,
               bytes_per_page=per_page, span=span)
    log(f"[size] pool {n} pages of {page_size} (worst case {full}); largest "
        f"bucket S={span} takes {total / 1e9:.3f} GB of {limit / 1e9:.3f} GB")
    CACHE_DIR.mkdir(exist_ok=True)
    tmp = path.with_suffix(f".{os.getpid()}.tmp")
    tmp.write_text(json.dumps(res))
    tmp.replace(path)
    return res
