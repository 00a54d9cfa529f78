"""Smoke run of the serving engine on a TPU at the full width of CodeQwen1.5-7B.

    python chip_smoke.py              # one chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4    # four chips: tp=4 against tp=1 only

Drives the normal serving path (``ContinuousBatchingEngine`` ->
``add_request`` -> ``run``) with the published widths of
``configs/codeqwen1_5_7b.py`` (32 layers, d_model 4096, Monarch paper policy,
bf16 activations and KV pages), random weights made from a fixed seed, and
eight seeded prompts of 64-512 tokens, 32 greedy new tokens each.

One chip runs three phases on the same requests:
  (a) engine defaults: XLA einsum Monarch, dense KV gather;
  (b) ``use_paged_kernel=True`` with ``MonarchSpec(backend="pallas")``;
  (c) phase (b) plus int8 Monarch weights and int8 KV pages.
Each phase must finish every request with 32 tokens and leave no page
held; (b) and (c) must dispatch the paged kernel on every step and carry
Mosaic kernels (``tpu_custom_call``) in their compiled step.  The logits of
one identical mixed step agree between (a), (b) and an fp32 reference of
the same weights within ``LOGITS_RTOL``.

``--chips 4`` runs only the tensor-parallel engine on a ("data", "model")
= (1, 4) mesh (KV heads split four ways, shard-mapped span kernel) and the
one-chip engine it is compared with, in this one process.

``max_len``, the page pool and ``chunk_size`` are sized before any engine
exists, from ``compiled.memory_analysis()`` of every span bucket the run can
dispatch.  Wall times printed here are smoke timings of a cold process, not
benchmark numbers.  The script exits non-zero, printing no result, when JAX
finds no TPU; its last stdout line is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.models import transformer as T  # noqa: E402

ARCH = "codeqwen1_5-7b"
SEED = 0
N_REQUESTS = 8
NEW_TOKENS = 32
PROMPT_LEN = (64, 512)
SLOTS = 8
PAGE_SIZE = 16
# share of the device's memory one compiled step may take (params, pool,
# temporaries); the rest is left for the runtime and the sampled tokens
MEMORY_SHARE = 0.9
# largest relative Frobenius distance allowed between the logits of one
# identical mixed step: two bf16 paths, or one of them and the fp32
# reference.  bf16 rounding grows through 32 layers of random weights; on a
# v5e chip (a) and (b) each lay 0.042 / 0.040 from the fp32 reference and
# 0.054 from each other, while a wrong page, head or block shows as O(1)
LOGITS_RTOL = 8e-2


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# phases and workload
# ---------------------------------------------------------------------------


def phases(cfg) -> dict:
    """Phase name -> (model config, engine options)."""
    pallas = dataclasses.replace(
        cfg, monarch=dataclasses.replace(cfg.monarch, backend="pallas"))
    return {
        "a": (cfg, {}),
        "b": (pallas, dict(use_paged_kernel=True)),
        "c": (pallas, dict(use_paged_kernel=True, quantize="int8",
                           kv_dtype="int8")),
    }


def make_prompts(vocab: int, seed: int = SEED, n: int = N_REQUESTS,
                 lengths=PROMPT_LEN) -> list:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=int(rng.integers(lengths[0],
                                                           lengths[1] + 1)))
            for _ in range(n)]


def make_params(cfg, seed: int = SEED):
    """Random full-width weights, generated on device and stored the way
    the engine serves them (``cfg.dtype``)."""
    return jax.jit(lambda k: T.serving_params(T.init_params(k, cfg), cfg))(
        jax.random.PRNGKey(seed))


def served(cfg, params, engine_kw: dict):
    """The (config, weights) the engine derives from ``engine_kw`` at load,
    as shapes only."""
    from repro.core.quant import BITS_BY_NAME
    from repro.models.decode_path import prepare_decode_params

    if engine_kw.get("use_paged_kernel"):
        cfg = dataclasses.replace(cfg, paged_kernel=True)
    shapes = jax.eval_shape(lambda p: T.serving_params(p, cfg), params)
    bits = BITS_BY_NAME.get(engine_kw.get("quantize"))
    if bits:
        shapes = jax.eval_shape(
            lambda p: prepare_decode_params(p, cfg, fuse=False, bits=bits),
            shapes)
    return cfg, shapes


# ---------------------------------------------------------------------------
# sizing from compiled.memory_analysis()
# ---------------------------------------------------------------------------


def compile_step(cfg, params, engine_kw: dict, n_pages: int, max_len: int,
                 span: int):
    """AOT-compile the engine's mixed step for one span bucket."""
    from repro.serving.engine import _mixed_step_jit

    cfg, weights = served(cfg, params, engine_kw)
    pool = jax.eval_shape(lambda: T.init_paged_pool(
        cfg, n_pages, PAGE_SIZE, kv_dtype=engine_kw.get("kv_dtype")))
    mp = math.ceil(max_len / PAGE_SIZE)
    i32 = jnp.int32
    rows = [((SLOTS, span), i32), ((SLOTS,), i32), ((SLOTS,), bool),
            ((SLOTS,), i32), ((SLOTS,), i32), ((SLOTS, mp), i32),
            ((SLOTS,), i32), ((SLOTS,), bool), ((SLOTS,), jnp.float32),
            ((SLOTS, 2), jnp.uint32)]
    args = [jax.ShapeDtypeStruct(s, d) for s, d in rows]
    t0 = time.perf_counter()
    compiled = _mixed_step_jit.lower(weights, pool, *args, cfg=cfg).compile()
    return compiled, time.perf_counter() - t0


def step_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def kernel_chunk(cfg, engine_kw: dict, longest: int) -> int:
    """Largest power-of-two span the paged kernel takes at this width
    (``kernels.ops.paged_dispatch``), capped at the longest prompt."""
    from repro.core.quant import KV_DTYPE_BYTES
    from repro.kernels.ops import paged_dispatch

    kv = engine_kw.get("kv_dtype") or (
        "bf16" if cfg.dtype == "bfloat16" else "fp32")
    chunk = 1 << (longest - 1).bit_length()
    while chunk > 1 and paged_dispatch(
            chunk, cfg.n_heads, cfg.hd, PAGE_SIZE, cfg.n_kv_heads,
            KV_DTYPE_BYTES[kv], quantized=kv == "int8") != "kernel":
        chunk //= 2
    return chunk


def size_run(cfg, params, plan: dict, prompts, limit: int) -> dict:
    """Pick ``max_len``, the pool and ``chunk_size`` for the phases in
    ``plan`` from the compiled step of every span bucket they can run."""
    longest = max(len(p) for p in prompts)
    max_len = PAGE_SIZE * math.ceil((longest + NEW_TOKENS) / PAGE_SIZE)
    n_pages = 1 + SLOTS * math.ceil(max_len / PAGE_SIZE)
    chunk = 1 << (longest - 1).bit_length()
    for pcfg, kw in plan.values():
        if kw.get("use_paged_kernel"):
            chunk = min(chunk, kernel_chunk(pcfg, kw, longest))
    report = {}
    for name, (pcfg, kw) in plan.items():
        span, seconds, rows = 1, 0.0, []
        while span <= chunk:
            compiled, dt = compile_step(pcfg, params, kw, n_pages, max_len,
                                        span)
            seconds += dt
            total = step_bytes(compiled)
            hlo_kernel = "tpu_custom_call" in compiled.as_text()
            rows.append((span, total, hlo_kernel))
            if total > MEMORY_SHARE * limit:
                chunk = span // 2
                break
            span *= 2
        report[name] = dict(compile_s=seconds, buckets=rows)
        log(f"[size] phase {name}: " + ", ".join(
            f"S={s}: {b / 1e9:.3f} GB" for s, b, _ in rows)
            + f"  (AOT compile {seconds:.1f} s)")
    if chunk < 1:
        raise RuntimeError("the decode step alone does not fit the device")
    log(f"[size] max_len={max_len} n_pages={n_pages} chunk_size={chunk} "
        f"(device limit {limit / 1e9:.3f} GB x {MEMORY_SHARE})")
    return dict(max_len=max_len, n_pages=n_pages, chunk=chunk,
                report=report)


# ---------------------------------------------------------------------------
# one phase
# ---------------------------------------------------------------------------


def bytes_in_use() -> list:
    """Per-device bytes in use (zeros where the backend reports none)."""
    return [(d.memory_stats() or {}).get("bytes_in_use", 0)
            for d in jax.devices()]


def run_phase(name: str, cfg, params, prompts, *, max_len: int,
              n_pages: int, chunk: int, engine_kw: dict,
              new_tokens: int = NEW_TOKENS, mesh=None) -> dict:
    """Serve ``prompts`` through a fresh engine and check the outcome:
    every request finishes with ``new_tokens`` tokens, the host pool is
    consistent and holds no page, and a kernel phase dispatched the paged
    kernel on every step.  Returns outputs, counters and the wall time."""
    from repro.serving import ContinuousBatchingEngine, SamplingParams

    before = bytes_in_use()
    eng = ContinuousBatchingEngine(
        cfg, params, max_slots=SLOTS, page_size=PAGE_SIZE, max_len=max_len,
        n_pages=n_pages, chunk_size=chunk, mesh=mesh, **engine_kw)
    jax.block_until_ready((eng.params, eng.pool))
    placed = [a - b for a, b in zip(bytes_in_use(), before)]
    reqs = [eng.add_request(p, SamplingParams(max_new_tokens=new_tokens))
            for p in prompts]
    t0 = time.perf_counter()
    eng.run()
    wall = time.perf_counter() - t0
    outs = [list(r.output_tokens) for r in reqs]
    bad = [i for i, o in enumerate(outs) if len(o) != new_tokens]
    if bad:
        raise AssertionError(f"phase {name}: requests {bad} did not finish "
                             f"with {new_tokens} tokens")
    eng.pool_host.check_invariants()
    ps = eng.pool_host.stats()
    if ps.unique_pages or ps.n_seqs or ps.free_pages != ps.n_pages:
        raise AssertionError(f"phase {name}: pages left held: {ps}")
    s = eng.stats
    if engine_kw.get("use_paged_kernel") and (
            s["kernel_dispatches"] == 0 or s["dense_fallbacks"] != 0):
        raise AssertionError(
            f"phase {name}: kernel_dispatches={s['kernel_dispatches']} "
            f"dense_fallbacks={s['dense_fallbacks']}")
    from repro.core.quant import tree_weight_bytes

    res = dict(outputs=outs, wall_s=wall, steps=eng.step_idx,
               kernel_dispatches=s["kernel_dispatches"],
               dense_fallbacks=s["dense_fallbacks"],
               param_bytes=tree_weight_bytes(eng.params),
               pool_bytes=tree_weight_bytes(eng.pool),
               placed_bytes=placed)
    del eng
    gc.collect()
    return res


def agreement(ref: list, other: list) -> str:
    """Greedy tokens equal position by position, and requests whose first
    token agrees: one early difference changes every later token of a
    request, so the first share says more about the logits."""
    a, b = np.asarray(ref), np.asarray(other)
    return (f"{float((a == b).mean()):.4f} of tokens, "
            f"{float((a[:, 0] == b[:, 0]).mean()):.4f} of first tokens")


def step_logits(cfg, engine_kw: dict, params, prompts, mesh=None,
                span: int = 64) -> np.ndarray:
    """Logits of one mixed step: every row prefills the first ``span``
    tokens of its prompt into a fresh pool, through the engine's own load
    transform and sharding (``mesh``)."""
    from repro.serving.device_kv import DeviceKV

    cfg, _ = served(cfg, params, engine_kw)
    params = T.serving_params(params, cfg)
    if mesh is not None:
        from repro.sharding.params import param_shardings

        params = jax.device_put(params, param_shardings(params, mesh))
    per_row = span // PAGE_SIZE
    kv = DeviceKV(cfg, 1 + SLOTS * per_row, PAGE_SIZE,
                  kv_dtype=engine_kw.get("kv_dtype"), mesh=mesh)
    tokens = np.stack([p[:span] for p in prompts[:SLOTS]]).astype(np.int32)
    pt = (1 + np.arange(SLOTS * per_row, dtype=np.int32)).reshape(SLOTS,
                                                                  per_row)
    fn = jax.jit(lambda p, t, s, n, pt, pool: T.paged_mixed_step(
        p, t, s, n, pt, pool, cfg)[0])
    args = (params, jnp.asarray(tokens), jnp.zeros((SLOTS,), jnp.int32),
            jnp.full((SLOTS,), span, jnp.int32), jnp.asarray(pt), kv.pool)
    if mesh is None:
        out = fn(*args)
    else:
        from repro.sharding.api import axis_rules

        with axis_rules(mesh):
            out = fn(*args)
    return np.asarray(out.astype(jnp.float32))


def reference_logits(cfg, params, prompts, span: int = 64) -> np.ndarray:
    """fp32 oracle of ``step_logits``: the same weights upcast, fp32
    activations and KV pages, XLA paths only, full-precision matmuls."""
    ref = dataclasses.replace(cfg, dtype="float32")
    with jax.default_matmul_precision("highest"):
        return step_logits(ref, {}, params, prompts, span=span)


def logits_distance(ref: np.ndarray, other: np.ndarray) -> dict:
    return dict(
        rel_frobenius=float(np.linalg.norm(other - ref)
                            / np.linalg.norm(ref)),
        max_abs=float(np.abs(other - ref).max()),
        ref_max_abs=float(np.abs(ref).max()),
        argmax_agreement=float((other.argmax(-1) == ref.argmax(-1)).mean()))


def check_logits(ref: np.ndarray, runs: dict) -> None:
    """Log two runs' logits of one step against the fp32 reference and
    against each other; fail when any distance exceeds ``LOGITS_RTOL``."""
    (n1, l1), (n2, l2) = runs.items()
    pairs = {f"{n1} vs fp32 reference": (ref, l1),
             f"{n2} vs fp32 reference": (ref, l2),
             f"{n2} vs {n1}": (l1, l2)}
    dist = {label: logits_distance(*p) for label, p in pairs.items()}
    for label, d in dist.items():
        log(f"[logits] one mixed step, {label}: {json.dumps(d)}")
    far = {k: d["rel_frobenius"] for k, d in dist.items()
           if not d["rel_frobenius"] <= LOGITS_RTOL}
    if far:
        raise AssertionError(f"logits beyond {LOGITS_RTOL}: {far}")


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------


def one_chip(cfg, params, prompts, limit: int) -> None:
    plan = phases(cfg)
    size = size_run(cfg, params, plan, prompts, limit)
    for name in ("b", "c"):
        if not all(k for _, _, k in size["report"][name]["buckets"]):
            raise AssertionError(f"phase {name}: compiled step has no "
                                 f"tpu_custom_call")
    results = {}
    for name, (pcfg, kw) in plan.items():
        res = run_phase(name, pcfg, params, prompts,
                        max_len=size["max_len"], n_pages=size["n_pages"],
                        chunk=size["chunk"], engine_kw=kw)
        results[name] = res
        peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
        log(f"[phase {name}] {res['steps']} steps, wall {res['wall_s']:.2f} s "
            f"(smoke timing of a cold process, not a benchmark); params "
            f"{res['param_bytes'] / 1e9:.3f} GB, pool "
            f"{res['pool_bytes'] / 1e9:.3f} GB, peak_bytes_in_use "
            f"{peak / 1e9:.3f} GB; kernel_dispatches="
            f"{res['kernel_dispatches']} dense_fallbacks="
            f"{res['dense_fallbacks']}")
    for name in ("b", "c"):
        log(f"[agree] greedy tokens ({name}) vs (a): " + agreement(
            results["a"]["outputs"], results[name]["outputs"]))
    check_logits(reference_logits(cfg, params, prompts),
                 {"(a)": step_logits(*plan["a"], params, prompts),
                  "(b)": step_logits(*plan["b"], params, prompts)})


def four_chips(cfg, params, prompts, limit: int) -> None:
    from repro.launch.mesh import make_host_mesh

    kw = dict(use_paged_kernel=True)
    plan = {"tp1": (cfg, kw)}
    size = size_run(cfg, params, plan, prompts, limit)
    mesh = make_host_mesh(model=4)
    results = {}
    for name, m in (("tp1", None), ("tp4", mesh)):
        res = run_phase(name, cfg, params, prompts, max_len=size["max_len"],
                        n_pages=size["n_pages"], chunk=size["chunk"],
                        engine_kw=kw, mesh=m)
        results[name] = res
        log(f"[{name}] {res['steps']} steps, wall {res['wall_s']:.2f} s "
            f"(smoke timing, not a benchmark); kernel_dispatches="
            f"{res['kernel_dispatches']} dense_fallbacks="
            f"{res['dense_fallbacks']}; bytes_in_use added per device by "
            f"loading the engine: "
            + ", ".join(f"{b / 1e9:.3f} GB" for b in res["placed_bytes"]))
    spread = results["tp4"]["placed_bytes"]
    if min(spread) < 0.5 * max(spread):
        raise AssertionError(f"tp4 placement is lopsided: {spread}")
    log("[agree] greedy tokens tp4 vs tp1: " + agreement(
        results["tp1"]["outputs"], results["tp4"]["outputs"]))
    check_logits(reference_logits(cfg, params, prompts),
                 {"tp1": step_logits(cfg, kw, params, prompts),
                  "tp4": step_logits(cfg, kw, params, prompts, mesh=mesh)})


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: tensor-parallel engine against tp=1 only")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    if len(jax.devices()) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(jax.devices())} devices")
    from repro.kernels import ops, paged
    from repro.launch.cache import use_compile_cache

    if ops._interpret() or paged._interpret():
        sys.exit("chip_smoke: kernels would run in interpret mode")
    cache = use_compile_cache()
    log(f"device_kind={dev.device_kind} platform={dev.platform} "
        f"count={len(jax.devices())} compile_cache={cache}")

    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params = make_params(cfg)
    jax.block_until_ready(params)
    log(f"[init] {ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.param_count() / 1e9:.3f} B params in {cfg.dtype}, "
        f"{time.perf_counter() - t0:.1f} s")
    prompts = make_prompts(cfg.vocab)
    log(f"[workload] {len(prompts)} prompts, lengths "
        f"{[len(p) for p in prompts]}, {NEW_TOKENS} greedy tokens each")
    limit = dev.memory_stats()["bytes_limit"]
    if args.chips == 4:
        four_chips(cfg, params, prompts, limit)
    else:
        one_chip(cfg, params, prompts, limit)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
