"""Size the page pool from the compiled step's memory analysis.

One engine iteration is one mixed step: a decode group of one position
per slot beside a span group of R prefill rows, each as wide as the
bucket of the scheduler's ``chunk_size``; the step compiles once per row
bucket R (0, then powers of two up to the slot count).  Its footprint is
the weights, the pool (twice: the layer scan writes a new pool beside the
old one) and the temporaries of the largest bucket, R = every slot.

The step is the engine's own: ``engine_step`` builds the engine with the
configuration's deployment settings under ``jax.eval_shape`` (nothing is
allocated on the device), records the arguments the engine's row-bucket
warm-up hands its step, and lowers the jit the engine dispatches over
them, whatever pool pytree and slot arrays the engine holds.  Nothing
here restates the step's signature or the pool's layout.

The largest bucket is compiled ahead of time at two small pool sizes (a
sixteenth and an eighth of every slot at ``max_len``); the footprint is
linear in the page count, so the largest pool that keeps the step under
``MEMORY_SHARE`` of the device is read off the line and then compiled once
more to confirm (the chip's compiler refuses a program that does not fit).
Nothing is hard-coded: a change to the program that frees device memory
gets more pages here.

The result is kept in ``.bench_cache/`` in the checkout, keyed by the
program's source, the configuration, the deployment, the mix and the
device, so only the first run of a checkout pays the three compiles.
"""

from __future__ import annotations

import hashlib
import json
import os
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".bench_cache"
# share of the device's memory one compiled step may take; the rest is for
# the runtime, the sampled tokens and the small page-table programs
MEMORY_SHARE = 0.9


def step_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def engine_step(cfg, weights, settings: dict, max_len: int, n_pages: int):
    """The engine's largest mixed step at ``n_pages``, as the engine built
    with ``settings`` would dispatch it: (the jit with its static
    arguments bound, the step's abstract arguments, the engine).  The
    engine is built and warmed under ``jax.eval_shape`` with its step
    replaced by a recorder, so its arrays are abstract; it serves only to
    read its settings (slots, pages per sequence, span width)."""
    from repro.serving import ContinuousBatchingEngine

    got = {}

    def build(params):
        engine = ContinuousBatchingEngine(cfg, params, max_len=max_len,
                                          n_pages=n_pages, **settings)
        calls = []

        def record(*args):
            calls.append(args)
            return (args[1],) + (None,) * 3

        got["step"], engine._mixed = engine._mixed, record
        engine._warm_row_buckets()
        got["engine"] = engine
        return max(calls, key=lambda a: sum(x.size for x in
                                            jax.tree_util.tree_leaves(a)))

    args = jax.eval_shape(build, jax.eval_shape(lambda w: w, weights))
    return got["step"], args, got["engine"]


def compile_engine_step(cfg, weights, settings: dict, max_len: int,
                        n_pages: int):
    """``engine_step`` compiled ahead of time."""
    step, args, _ = engine_step(cfg, weights, settings, max_len, n_pages)
    return step.func.lower(*args, **step.keywords).compile()


def _bytes_or_none(cfg, weights, settings, max_len, n_pages):
    """The step's footprint at ``n_pages``, or None where the chip's
    compiler refuses it for want of memory."""
    try:
        return step_bytes(compile_engine_step(cfg, weights, settings,
                                              max_len, n_pages))
    except jax.errors.JaxRuntimeError as e:
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        return None


def _key(cfg, settings, max_len, span, limit) -> str:
    h = hashlib.sha256()
    src = ROOT / "src"
    for p in sorted(src.rglob("*.py")):
        h.update(str(p.relative_to(src)).encode())
        h.update(p.read_bytes())
    dev = jax.devices()[0]
    other = sorted((k, v) for k, v in settings.items()
                   if k not in ("max_slots", "page_size"))
    h.update(repr((cfg, settings["max_slots"], settings["page_size"],
                   max_len, span, limit, dev.device_kind, jax.__version__)
                  + ((tuple(other),) if other else ())).encode())
    return h.hexdigest()[:24]


def size_pool(cfg, weights, settings: dict, max_len: int,
              log=print) -> dict:
    """Largest page count (at most every slot at ``max_len``) whose
    largest step, in the engine built with the deployment ``settings``,
    fits ``MEMORY_SHARE`` of the device."""
    try:
        return _size_pool(cfg, weights, settings, max_len, log)
    finally:
        # Forget what sizing traced, whether it compiled or read the cache,
        # so every run builds the engine's programs from the same state.
        # The engine would reuse the traced pieces, whose op metadata points
        # here, and the persistent cache, keyed with that metadata, would
        # hold programs that the next run, which reads the pool size from
        # CACHE_DIR, never builds: it would compile them anew.
        jax.clear_caches()


def _size_pool(cfg, weights, settings, max_len, log) -> dict:
    _, _, engine = engine_step(cfg, weights, settings, max_len, 2)
    full = 1 + engine.max_slots * engine.max_pages_per_seq
    stats = jax.devices()[0].memory_stats()
    if stats is None:      # a backend that reports no memory: the CPU tests
        return dict(n_pages=full, step_bytes=0, limit_bytes=0,
                    bytes_per_page=0.0, span=0)
    limit = stats["bytes_limit"]
    span = engine.span_bucket
    settings = dict(settings, max_slots=engine.max_slots,
                    page_size=engine.page_size)
    key = _key(cfg, settings, max_len, span, limit)
    path = CACHE_DIR / f"pool-{key}.json"
    if path.exists():
        return json.loads(path.read_text())
    budget = MEMORY_SHARE * limit

    def footprint(n):
        return _bytes_or_none(cfg, weights, settings, max_len, n)

    # two pool sizes small enough to compile, then the line through them
    lo, hi = max(2, full // 16), max(3, full // 8)
    b_lo, b_hi = footprint(lo), footprint(hi)
    if b_lo is None or b_hi is None:
        raise RuntimeError("the mixed step does not fit the device even "
                           f"with a pool of {lo} pages")
    per_page = (b_hi - b_lo) / (hi - lo)
    n = min(full, int((budget - b_lo) / per_page) + lo)
    while True:
        if n < lo:
            raise RuntimeError("the mixed step does not fit the device")
        total = footprint(n)
        if total is not None and total <= budget:
            break
        n = int(n * 0.97)
    res = dict(n_pages=n, step_bytes=total, limit_bytes=limit,
               bytes_per_page=per_page, span=span)
    log(f"[size] pool {n} pages of {engine.page_size} (worst case {full}); "
        f"largest bucket S={span} takes {total / 1e9:.3f} GB of "
        f"{limit / 1e9:.3f} GB")
    CACHE_DIR.mkdir(exist_ok=True)
    tmp = path.with_suffix(f".{os.getpid()}.tmp")
    tmp.write_text(json.dumps(res))
    tmp.replace(path)
    return res
