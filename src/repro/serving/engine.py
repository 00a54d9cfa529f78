"""Serving engines: continuous batching over the paged KV pool, plus the
legacy single-batch ``ServeEngine`` kept as a compat shim.

``ContinuousBatchingEngine`` is the tentpole runtime.  Every iteration is
ONE jitted mixed forward (``models.transformer.paged_mixed_step``): each
scheduled sequence contributes a variable-length token span — a prefill
chunk, the tail of a chunked prompt, or a single decode token — so long
prompts no longer head-of-line-block the decode batch; the scheduler
(``scheduler.plan_step``) sizes the chunks around the in-flight decodes
under token/page/latency budgets priced by the cost model.

  * requests join and leave the slot batch between steps (iteration-level
    scheduling) — no batch restarts, no separate prefill forward, no
    padding every slot to the longest request;
  * KV pages are allocated incrementally as each sequence's
    ``num_computed_tokens`` cursor advances — no conservative
    prompt + max_new reservation.  When the pool runs dry mid-flight the
    lowest-priority sequence is *preempted* back to WAITING (page refcounts
    released, emitted tokens kept, prefix re-matched on resume — greedy
    output is token-identical, and ``resume_key`` keeps sampled runs on
    their original PRNG stream);
  * prompt prefixes are shared through the pool's refcounted prefix trie
    (``prefix_sharing=True``): admission starts the cursor at the matched
    length (shared full pages = refcount bumps, zero prefill tokens), a
    partially-cached or about-to-be-written shared page is forked
    copy-on-write (one private page + an on-device page copy, dispatched
    before the fork's first forward), and full pages are committed back to
    the trie as the prefill cursor crosses their boundary.  Span writes are
    provably confined to exclusively-owned pages: host-side by
    ``pool.assert_writable`` on every span, device-side by a write-mask
    derived from the fork point (``write_start``);
  * KV pages are stored at the engine's ``kv_dtype`` ("fp32" | "bf16" |
    "int8"; None inherits the model dtype): int8 pools quantize fresh K/V
    spans on device before the page write (one fp32 scale per
    (page, head), K and V independent — ``core.quant``), dequantize
    in-kernel on read, and — sized by ``pool_bytes`` — hold ~4x the fp32
    page count under the same byte budget, so the same workload preempts
    less and shares deeper;
  * sampling, token feedback and the page-table gather happen on device;
    only rows whose span reaches the end of their known tokens sample.
    Sampled tokens are harvested with a one-step lag: step N+1 is
    dispatched before step N's results are read back, keeping transfers
    off the critical path (the host never blocks the dispatch chain);
  * the engine is *observable*: ``stats`` is a typed ``EngineStats`` view
    over a ``MetricsRegistry`` (dict-compatible — existing call sites keep
    working), per-request lifecycle timestamps land on the ``Request``
    (token stamps at device-sync harvest time, never dispatch time — the
    lagged harvest would otherwise antedate them), per-iteration gauges
    track batch composition and pool pressure, ``trace=`` brackets the
    engine phases (plan / admit / dispatch / sync / harvest) with Chrome
    trace-event spans loadable in Perfetto, and a ``Calibration`` pairs
    each step's cost-model prediction with measured wall time.
    ``metrics=False`` keeps only the raw counters; with tracing off the
    span hooks are no-op singletons — near-zero overhead by construction.
  * the engine is *fault-tolerant*: per-request ``deadline_s`` /
    ``max_queue_wait_s`` budgets are enforced by a per-step deadline sweep
    and by scheduler admission control (expired requests finish as
    TIMEOUT / SHED with pages freed refcount-correctly), ``cancel()``
    aborts a request at any lifecycle stage — always *after* draining the
    in-flight dispatch chain, so the one-step harvest lag can never
    resurrect a torn-down sequence — ``snapshot()`` /
    ``ContinuousBatchingEngine.restore()`` round-trip the complete
    serving state (queues, cursors, page tables, prefix trie, device KV)
    through ``checkpoint/store.py``, and a ``fault_injector`` hook lets
    ``serving/faults.py`` drive chaos testing (pool exhaustion, dispatch
    failure, simulated crashes, clock skew) against the recovery
    invariants.  See ``serving/__init__`` for the recovery contract.
  * the engine is *tensor-parallel*: ``mesh=`` serves the model sharded
    over a ``("data", "model")`` mesh — Monarch/attention factors placed
    by the ``sharding/params.py`` suffix rules, activations constrained by
    the ``logical()`` tags in ``models/layers.py``, and the paged pool
    owned by a ``DeviceKV`` whose page buffers and quant-scale rows are
    split on the KV-head axis (see ``serving/device_kv.py`` for the
    ownership contract).  Scheduling, preemption, prefix sharing and COW
    stay host-global (logical pages); only the bytes behind each page are
    per-shard.  The mixed step still compiles ONCE (per span bucket) —
    ``_mixed_step_tp_jit`` bakes the mesh in as a static arg and GSPMD
    partitions the single forward.  ``mesh=None`` is byte-identical to
    the single-device engine.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import math
import os
import time
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serving.faults import DispatchFailure
from repro.serving.kv_pool import PagedKVPool, PoolOOM, SINK_PAGE
from repro.serving.metrics import (Calibration, EngineStats,
                                   LATENCY_MS_BUCKETS, MetricsRegistry,
                                   TOKEN_BUCKETS)
from repro.serving.request import (FinishReason, Request, RequestState,
                                   SamplingParams, Sequence)
from repro.serving.scheduler import (CostModel, IterationScheduler,
                                     SchedulerConfig, StepPlan)
from repro.serving.tracing import NULL_TRACER, ChromeTracer


@dataclasses.dataclass
class GenerationConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0     # 0 => greedy
    eos_id: Optional[int] = None
    seed: int = 0


def _sample_rows(logits: jax.Array, temps: jax.Array, keys: jax.Array
                 ) -> jax.Array:
    """Per-row sampling with per-row keys ((B,2) uint32, one PRNG stream per
    request): greedy where temps <= 0, else temperature.  The categorical
    draw sits behind a cond so all-greedy batches skip it."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def drawn(_):
        safe = jnp.maximum(temps, 1e-6)[:, None]
        d = jax.vmap(jax.random.categorical)(keys, logits / safe)
        return jnp.where(temps <= 0.0, greedy, d.astype(jnp.int32))

    return jax.lax.cond(jnp.any(temps > 0.0), drawn, lambda _: greedy, None)


def _split_rows(keys: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(B,2) per-row keys -> (draw keys, carried keys)."""
    s = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    return s[:, 0], s[:, 1]


def _bucket(n: int, lo: int = 1) -> int:
    return max(lo, 1 << (n - 1).bit_length())


# Module-level jits with the (frozen, hashable) ModelConfig as a static arg:
# every engine instance of the same config shares one compiled step, so
# constructing an engine never retraces.  The mixed step recompiles only per
# span bucket (power-of-two padded max span), not per batch composition.


def _mixed_step_body(params, pool, chunk_tok, tok_dev, use_dev, start, span,
                     pt, wstart, sample_mask, temp, keys, cfg):
    """ONE unified engine iteration over the slot batch.

    ``chunk_tok`` (B, S) carries host-known span tokens (prefill chunks);
    rows flagged ``use_dev`` are decodes whose single input token is the
    previous step's on-device sample (``tok_dev``), so the dispatch chain
    never waits on a host readback.  Rows whose span reaches the end of
    their known tokens (``sample_mask``) draw a token; everyone else keeps
    their device token and PRNG stream untouched — per-request streams
    advance only on draws, so chunking never perturbs sampling.
    ``wstart`` (B,) is each row's copy-on-write fork point: positions below
    it live in shared prefix pages and are never written (mask-enforced in
    the kernel-side page write, independent of host bookkeeping).  The
    draw carries the ``sample`` scope (``serving/tracing.py``)."""
    col0 = jnp.where(use_dev, tok_dev, chunk_tok[:, 0])
    tokens = chunk_tok.at[:, 0].set(col0)
    logits, pool = T.paged_mixed_step(params, tokens, start, span, pt, pool,
                                      cfg, write_start=wstart)
    with jax.named_scope("sample"):
        draw, carry = _split_rows(keys)
        sampled = _sample_rows(logits, temp, draw)
        tok_new = jnp.where(sample_mask, sampled, tok_dev)
        keys_new = jnp.where(sample_mask[:, None], carry, keys)
    return pool, sampled, tok_new, keys_new


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def _mixed_step_jit(params, pool, chunk_tok, tok_dev, use_dev, start, span,
                    pt, wstart, sample_mask, temp, keys, *, cfg):
    return _mixed_step_body(params, pool, chunk_tok, tok_dev, use_dev, start,
                            span, pt, wstart, sample_mask, temp, keys, cfg)


@functools.partial(jax.jit, static_argnames=("cfg", "mesh"),
                   donate_argnums=(1,))
def _mixed_step_tp_jit(params, pool, chunk_tok, tok_dev, use_dev, start, span,
                       pt, wstart, sample_mask, temp, keys, *, cfg, mesh):
    """Tensor-parallel mixed step: same body, compiled under the mesh.

    A SEPARATE jit from ``_mixed_step_jit`` on purpose: the ``logical()``
    tags in ``models/layers.py`` read the thread-local mesh at TRACE time,
    and jax's trace cache is keyed on avals, not shardings — sharing one
    jit between the tp=1 and tp>1 paths could silently reuse a trace made
    without the constraints.  With the (hashable) mesh as a static arg the
    constraint-baked trace is cached per mesh, the tp=1 path stays
    bit-identical to the pre-mesh code, and every engine iteration is
    still ONE compiled mixed forward — GSPMD partitions it from the param/
    pool input shardings plus the activation constraints."""
    from repro.serving.device_kv import kv_shard_size, pool_shardings
    from repro.sharding.api import axis_rules

    with axis_rules(mesh):
        pool, sampled, tok_new, keys_new = _mixed_step_body(
            params, pool, chunk_tok, tok_dev, use_dev, start, span, pt,
            wstart, sample_mask, temp, keys, cfg)
        # pin the output pool to the DeviceKV contract placement — without
        # this GSPMD is free to re-shard a replicated (kv_shard=1) pool on
        # whatever layout the attention partitioning prefers, drifting the
        # placement step over step
        shardings = pool_shardings(pool, mesh, kv_shard_size(cfg, mesh))
        pool = jax.tree_util.tree_map(jax.lax.with_sharding_constraint,
                                      pool, shardings)
        return pool, sampled, tok_new, keys_new


@functools.partial(jax.jit, donate_argnums=(0,))
def _cow_copy_jit(pool, src, dst):
    """Device half of COW forks: copy pages ``src`` -> ``dst`` everywhere."""
    return T.cow_copy_pages(pool, src, dst)


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(2,))
def _ring_decode_jit(params, tok, cache, *, cfg):
    return T.decode_step(params, tok, cache, cfg)


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(2,))
def _ring_prefill_jit(params, tokens, cache, *, cfg):
    return T.prefill_with_cache(params, tokens, cache, cfg)


class ContinuousBatchingEngine:
    """Iteration-scheduled serving over a paged KV pool (attn stacks)."""

    def __init__(self, cfg: ModelConfig, params, *, max_slots: int = 8,
                 page_size: int = 16, max_len: int = 512,
                 n_pages: Optional[int] = None,
                 pool_bytes: Optional[int] = None,
                 chunk_size: Optional[int] = None,
                 scheduler_cfg: Optional[SchedulerConfig] = None,
                 cost_model: Optional[CostModel] = None,
                 use_paged_kernel: bool = False,
                 quantize: Optional[str] = None,
                 fuse_projections: bool = False,
                 prefix_sharing: bool = True,
                 kv_dtype: Optional[str] = None,
                 metrics: bool = True,
                 trace: Union[bool, str, os.PathLike, None] = None,
                 fault_injector=None,
                 heartbeat=None, heartbeat_rank: int = 0,
                 mesh: Optional[jax.sharding.Mesh] = None):
        if cfg.layer_kind != "attn":
            raise ValueError(
                "continuous batching needs an attn stack; SSM/hybrid models "
                "serve through the legacy ServeEngine")
        if use_paged_kernel:
            cfg = dataclasses.replace(cfg, paged_kernel=True)
        # decode fast path, applied once at load: exact QKV/gate-up fusion,
        # then per-block int8/int4 quantization of the Monarch factors
        # (models/decode_path.py).  The jitted step below consumes the
        # transformed tree unchanged — layers dispatch on the param keys.
        # NOTE on backends: the in-kernel-dequant Pallas path engages when
        # cfg.monarch.backend == "pallas" (the TPU deployment); with the
        # default "einsum" backend quantized factors dequantize per call,
        # which compresses storage and the cost-model-priced admission
        # (weight bytes), not CPU wall clock.
        from repro.core.quant import BITS_BY_NAME

        # served weights live in the activation dtype: a bf16 config holds
        # 2-byte weights on device and multiplies at the MXU's bf16 rate
        # (an fp32 config is unchanged).  Cast before fusion/quantization,
        # so int8 scales are taken from the weights actually served.
        params = T.serving_params(params, cfg)
        if quantize is not None and quantize not in BITS_BY_NAME:
            raise ValueError(
                f"quantize must be one of {sorted(BITS_BY_NAME)} or None, "
                f"got {quantize!r}")
        if fuse_projections or quantize:
            from repro.models.decode_path import prepare_decode_params

            params = prepare_decode_params(
                params, cfg, fuse=fuse_projections,
                bits=BITS_BY_NAME.get(quantize))
        self.weight_bits = BITS_BY_NAME.get(quantize, 32)
        self.cfg = cfg
        # -- tensor parallelism over a ("data", "model") mesh --------------
        # Params are placed by the path-suffix rules (sharding/params.py):
        # Monarch stage-1 block-rows and attention heads over "model",
        # stage-2 contractions as partial sums GSPMD all-reduces.  The
        # sharding is applied AFTER fusion/quantization so fused keys
        # (wqkv/wkv/w1g) and quantized factors land under the same rules
        # (unmatched leaves replicate — always correct).  mesh=None keeps
        # the single-device path byte-for-byte.
        self.mesh = mesh
        self.tp = 1
        if mesh is not None:
            if "model" not in mesh.axis_names:
                raise ValueError(
                    f"engine mesh needs a 'model' axis, got {mesh.axis_names}")
            self.tp = dict(mesh.shape)["model"]
            from repro.sharding.params import param_shardings

            params = jax.device_put(params, param_shardings(params, mesh))
        self.params = params
        self.page_size = page_size
        self.max_len = max_len
        self.max_pages_per_seq = math.ceil(max_len / page_size)
        # KV page width: None inherits the model dtype (legacy behavior);
        # "int8" serves quantized pages end to end — per-(page, head) fp32
        # scales on device, in-kernel dequant on read, and ~4x the page
        # count under the same byte budget
        from repro.core.quant import KV_DTYPE_BYTES, kv_page_bytes

        if kv_dtype is not None and kv_dtype not in KV_DTYPE_BYTES:
            raise ValueError(
                f"kv_dtype must be one of {sorted(KV_DTYPE_BYTES)} or None, "
                f"got {kv_dtype!r}")
        self.kv_dtype = kv_dtype or (
            "bf16" if cfg.dtype == "bfloat16" else "fp32")
        page_bytes = kv_page_bytes(cfg.n_layers, cfg.n_kv_heads, cfg.hd,
                                   page_size, self.kv_dtype)
        # per-shard physical weight of one logical page: each model-axis
        # shard stores only its own KV heads' rows (and scale entries), so
        # a byte budget — a PER-SHARD HBM budget under a mesh — divides by
        # the smaller per-shard page footprint and yields ~kv_shard x the
        # page count at tp=kv_shard
        from repro.serving.device_kv import DeviceKV, kv_shard_size

        kv_shard = kv_shard_size(cfg, mesh)
        shard_page_bytes = kv_page_bytes(
            cfg.n_layers, cfg.n_kv_heads // kv_shard, cfg.hd, page_size,
            self.kv_dtype)
        if n_pages is not None and pool_bytes is not None:
            raise ValueError(
                "pass n_pages (a page count) OR pool_bytes (a byte budget "
                "the kv_dtype converts into pages), not both")
        if n_pages is None:
            if pool_bytes is not None:
                # fixed byte budget -> dtype-aware page count: the knob the
                # kv_quant benchmark sweeps (int8 ~4x the fp32 pages)
                n_pages = 1 + max(1, pool_bytes // shard_page_bytes)
            else:  # worst case: every slot at max_len, plus sink
                n_pages = 1 + max_slots * self.max_pages_per_seq
        self.pool_host = PagedKVPool(n_pages, page_size,
                                     self.max_pages_per_seq,
                                     kv_dtype=self.kv_dtype,
                                     page_bytes=page_bytes,
                                     kv_shard=kv_shard)
        self.kv = DeviceKV(cfg, n_pages, page_size, kv_dtype=kv_dtype,
                           mesh=mesh)
        self.prefix_sharing = prefix_sharing
        sc = scheduler_cfg or SchedulerConfig()
        sc = dataclasses.replace(sc, max_slots=max_slots,
                                 prefix_sharing=prefix_sharing)
        if chunk_size is not None:
            sc = dataclasses.replace(sc, chunk_size=chunk_size)
        self.scheduler = IterationScheduler(sc, cost_model)

        S, MP = max_slots, self.max_pages_per_seq
        self.max_slots = S
        self._tok = jnp.zeros((S,), jnp.int32)
        self._temp = jnp.zeros((S,), jnp.float32)
        self._pt = jnp.full((S, MP), SINK_PAGE, jnp.int32)
        self._wstart = jnp.zeros((S,), jnp.int32)   # per-slot COW fork point
        self._keys = jnp.zeros((S, 2), jnp.uint32)  # per-request PRNG streams

        self.waiting: collections.deque[Request] = collections.deque()
        self.running: dict[int, Sequence] = {}          # slot -> Sequence
        self._free_slots = list(range(S - 1, -1, -1))
        self._pt_dirty: set[int] = set()   # slots whose page table changed
        self._admit_stamp = itertools.count()           # priority order
        self._pending: list[dict] = []                  # un-harvested steps
        self.step_idx = 0

        # -- observability: registry-backed stats, spans, calibration ------
        # The registry (and the EngineStats counters over it) always exists
        # — engine internals and every existing test/benchmark read
        # ``stats`` — while ``metrics=False`` turns off the EXTRA per-step
        # work: lifecycle histograms, pool gauges, the dispatch log and the
        # step calibration.  ``trace`` is off by default; a truthy value
        # collects Chrome trace events (a str/PathLike doubles as the
        # default ``tracer.save()`` path).
        self.registry = MetricsRegistry()
        self.stats = EngineStats(self.registry)
        self.metrics_enabled = bool(metrics)
        if trace:
            path = trace if isinstance(trace, (str, os.PathLike)) else None
            self.tracer = ChromeTracer(path=path)
        else:
            self.tracer = NULL_TRACER
        self.calibration = Calibration(
            "engine_step", self.registry if self.metrics_enabled else None)
        # (step_idx, req_id, kind, n_tokens) per executed span — the audit
        # log tests reconcile against the decode/prefill token counters
        self.dispatch_log: list[tuple[int, int, str, int]] = []
        if self.metrics_enabled:
            h, g = self.registry.histogram, self.registry.gauge
            self._h_ttft = h("request.ttft_ms", LATENCY_MS_BUCKETS)
            self._h_itl = h("request.itl_ms", LATENCY_MS_BUCKETS)
            self._h_queue_wait = h("request.queue_wait_ms",
                                   LATENCY_MS_BUCKETS)
            self._h_e2e = h("request.e2e_ms", LATENCY_MS_BUCKETS)
            self._h_cached = h("request.cached_tokens", TOKEN_BUCKETS)
            self._h_cow = h("request.cow_pages", (0.0, 1.0, 2.0, 4.0))
            self._h_batch = h("step.batch_size",
                              (0.0, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0))
            self._h_chunk = h("step.prefill_tokens", TOKEN_BUCKETS)
            self._g_queue = g("sched.queue_depth")
            self._g_free = g("pool.free_pages")
            self._g_shared = g("pool.shared_pages")
            self._g_cached = g("pool.cached_pages")
            self._g_held = g("pool.held_pages")
            self._g_evict = g("pool.cache_evictions")
        if mesh is None:
            self._mixed = functools.partial(_mixed_step_jit, cfg=self.cfg)
        else:
            self._mixed = functools.partial(_mixed_step_tp_jit, cfg=self.cfg,
                                            mesh=mesh)

        # -- fault tolerance ------------------------------------------------
        # ``_clock`` is THE time source for lifecycle stamps, deadline
        # sweeps and queue-wait shedding (``serving/faults.py`` skews it to
        # test deadline handling; the calibration above keeps raw
        # perf_counter so measured step durations never inherit the skew).
        self._clock = time.perf_counter
        self.faults = fault_injector
        # reported step-time multiplier for the fleet StragglerMonitor
        # (the "straggle" fault inflates it; 1.0 = honest wall time)
        self.straggle_factor = 1.0
        # optional liveness reporting: ``heartbeat.report(rank, step)`` is
        # called once per step — ``ft.coordinator.EngineSupervisor`` watches
        # it and recovers a quiet engine from its last published snapshot
        self.heartbeat = heartbeat
        self.heartbeat_rank = heartbeat_rank
        # requests finished outside _step_inner (``cancel()``, the drains
        # it triggers) surface through the next ``step()``'s return value
        self._overflow: list[Request] = []

    # -- device KV ownership -----------------------------------------------
    # The pool pytree lives in DeviceKV (placement, snapshot transfer, the
    # per-shard invariant); the property keeps the mixed step's
    # donate-and-replace idiom — and every existing call site — unchanged.

    @property
    def pool(self):
        return self.kv.pool

    @pool.setter
    def pool(self, value):
        self.kv.pool = value

    # -- request intake ----------------------------------------------------

    def add_request(self, prompt, sampling: Optional[SamplingParams] = None,
                    on_token=None) -> Request:
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        req = Request(prompt=prompt, sampling=sampling or SamplingParams(),
                      on_token=on_token)
        if req.sampling.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if req.max_total_len > self.max_len:
            raise PoolOOM(
                f"prompt+max_new={req.max_total_len} exceeds max_len="
                f"{self.max_len}")
        need = self.pool_host.pages_for(req.max_total_len)
        if need > self.pool_host.n_pages - 1:
            # even alone in the pool it could never finish: no schedule (or
            # preemption pattern) can serve it
            raise PoolOOM(
                f"request needs {need} pages; pool has "
                f"{self.pool_host.n_pages - 1} total")
        if self.prefix_sharing:
            # trie lookup at intake: an early hint for callers/logging (the
            # authoritative match re-runs at admission — the trie may gain
            # or lose entries while the request waits in the queue)
            req.num_cached_tokens = self.pool_host.match_prefix(
                req.known_tokens).n_tokens
        req.arrived_step = self.step_idx
        req.t_arrival = req.t_enqueued = req.mark("arrived", self._clock())
        self.waiting.append(req)
        if self.metrics_enabled:
            self._g_queue.set(len(self.waiting))
        return req

    def readmit(self, req: Request) -> Request:
        """Adopt an EXISTING request — typically migrated off a failed
        replica — into this engine's waiting queue via the preemption
        contract: cursor reset (its KV lives on the dead engine, recompute
        on resume), emitted tokens / ``resume_key`` / budgets / priority
        kept.  ``t_arrival`` is preserved, so a ``deadline_s`` budget keeps
        counting from the original arrival; the queue-wait clock restarts
        (the migration is scheduler latency the request should not be shed
        for)."""
        if req.state is RequestState.FINISHED:
            raise ValueError(
                f"request {req.req_id} already finished; nothing to readmit")
        if req.max_total_len > self.max_len:
            raise PoolOOM(
                f"prompt+max_new={req.max_total_len} exceeds max_len="
                f"{self.max_len}")
        need = self.pool_host.pages_for(req.max_total_len)
        if need > self.pool_host.n_pages - 1:
            raise PoolOOM(
                f"request needs {need} pages; pool has "
                f"{self.pool_host.n_pages - 1} total")
        req.state = RequestState.WAITING
        req.num_computed_tokens = 0
        req.num_cached_tokens = 0
        req.arrived_step = self.step_idx
        now = self._clock()
        if req.t_arrival < 0:
            req.t_arrival = now
        req.t_enqueued = req.mark("migrated", now)
        self.waiting.append(req)
        if self.metrics_enabled:
            self._g_queue.set(len(self.waiting))
        return req

    def has_work(self) -> bool:
        return bool(self.waiting or self.running or self._pending
                    or self._overflow)

    # -- one scheduler iteration -------------------------------------------

    def step(self) -> list[Request]:
        """Plan and dispatch ONE mixed forward (decode tokens + prefill
        chunks), harvest the previous one, evict finished sequences.
        Returns requests finished this call (including any aborted by
        ``cancel()``, the deadline sweep, or admission-control shedding)."""
        self.step_idx += 1
        if self.faults is not None:
            self.faults.on_step(self)
        if self.heartbeat is not None:
            self.heartbeat.report(self.heartbeat_rank, self.step_idx,
                                  now=self._clock())
        t0 = time.perf_counter()
        pred0 = self.stats["sim_latency_ns"]
        with self.tracer.span("step", step=self.step_idx):
            finished = self._step_inner()
        if self.metrics_enabled:
            # calibrate the cost model: pair this step's predicted ns (what
            # _dispatch charged to sim_latency_ns) with measured wall time.
            # Steps that dispatched nothing predict 0 and are skipped.
            pred = self.stats["sim_latency_ns"] - pred0
            if pred > 0:
                self.calibration.record(pred,
                                        (time.perf_counter() - t0) * 1e9)
        return finished

    def _step_inner(self) -> list[Request]:
        finished: list[Request] = []
        # surface requests finished outside the step loop (cancel() and the
        # drains it triggers) through this step's return value
        if self._overflow:
            finished.extend(self._overflow)
            self._overflow.clear()
        finished.extend(self._sweep_deadlines(self._clock()))

        plan = self._plan()
        if plan.preemptions:
            # drain every in-flight step first: a victim's already-dispatched
            # sample must land (and its PRNG carry settle) before its state
            # is torn down — then replan, because the drain may have finished
            # sequences and freed enough pages to avoid evicting anyone
            finished.extend(self.drain())
            plan = self._plan()
            if plan.preemptions:
                for seq in plan.preemptions:
                    self._preempt(seq)
                # replan once more: victims now sit at the queue FRONT, so
                # admissions are decided against the post-eviction queue (a
                # victim may even re-join immediately with whatever pages the
                # mandatory decodes left over).  The packing just proven
                # feasible still is — no further preemption can be needed.
                plan = self._plan()
                assert not plan.preemptions, "preemption did not converge"

        # admission control: the final plan's sheds are WAITING requests
        # past their queue-wait budget that still could not be admitted —
        # they hold no pages, so aborting them is pure queue surgery
        for req in plan.sheds:
            try:
                self.waiting.remove(req)
            except ValueError:
                continue   # cancelled between plan and execution
            self._finish_abort(req, FinishReason.SHED)
            finished.append(req)
        if plan.degraded:
            self.stats["degraded_chunks"] += plan.degraded
        if plan.prefix_deferred:
            self.stats["prefix_deferrals"] += plan.prefix_deferred

        spans = list(plan.spans)
        # reserve the mandatory decodes' pages BEFORE admissions touch the
        # pool: an admission's COW fork (or a trie-drift re-match) may draw
        # pages the plan did not charge it for, and the shrink logic in
        # _dispatch can only soften prefill spans — a decode must never
        # find its page gone
        for seq, n in spans:
            if seq.request.state is RequestState.RUNNING:
                new = self.pool_host.extend(seq.req_id, seq.num_computed + n)
                if new:
                    seq.page_ids.extend(new)
                    self._pt_dirty.add(seq.slot)
        spans.extend(self._admit(plan.admissions))
        if spans:
            try:
                self._dispatch(spans)
            except DispatchFailure:
                # recover by the PR 3 preemption contract: land every
                # in-flight step (PRNG carries settle), then evict ALL
                # residents to WAITING with pages freed and cursors reset —
                # the failed dispatch enqueued no device work, so recompute
                # on resume reproduces the exact token streams
                self.stats["dispatch_failures"] += 1
                self.tracer.instant("dispatch_failure", step=self.step_idx)
                finished.extend(self.drain())
                for seq in sorted(self.running.values(),
                                  key=lambda s: (s.request.sampling.priority,
                                                 -s.admit_order)):
                    self._preempt(seq)
                return finished

        if self.faults is not None:
            self.faults.on_harvest(self, "before")
        # harvest everything but the step just dispatched (one-step lag)
        keep_last = 1 if spans else 0
        while len(self._pending) > keep_last:
            finished.extend(self._harvest(self._pending.pop(0)))
        if self.faults is not None:
            self.faults.on_harvest(self, "after")
        return finished

    # -- deadlines / cancellation ------------------------------------------

    def drain(self) -> list[Request]:
        """Harvest every in-flight dispatched step (device sync).  The
        engine dispatches step N+1 before step N's tokens are read back;
        any state teardown — cancel, preemption, snapshot — must land those
        tokens first, or the lag could resurrect (or write into) state the
        teardown just released."""
        done: list[Request] = []
        while self._pending:
            done.extend(self._harvest(self._pending.pop(0)))
        return done

    def cancel(self, req_id: int,
               reason: FinishReason = FinishReason.ABORTED) -> bool:
        """Abort a request by id (client disconnect).  A WAITING request
        leaves the queue immediately; a resident sequence is torn down only
        after ``drain()`` — see there — and its pages are released
        refcount-correctly (shared prefix pages survive with their other
        holders).  Returns True if the request was cancelled, False if the
        id is unknown or already finished (a second cancel of the same id
        is a no-op, not an error)."""
        for req in list(self.waiting):
            if req.req_id == req_id:
                self.waiting.remove(req)
                self._finish_abort(req, reason)
                self._overflow.append(req)
                return True
        seq = next((s for s in self.running.values()
                    if s.req_id == req_id), None)
        if seq is None:
            return False
        self._overflow.extend(self.drain())
        req = seq.request
        if (req.state is RequestState.FINISHED
                or self.running.get(seq.slot) is not seq):
            return False   # the drain finished it before the cancel landed
        self._finish_abort(req, reason)
        self._evict(seq)
        self._overflow.append(req)
        return True

    def _sweep_deadlines(self, now: float) -> list[Request]:
        """Drive every request past its ``deadline_s`` to FINISHED/TIMEOUT:
        queued requests leave the queue, resident sequences are evicted
        (after the pending-harvest drain) with pages freed immediately."""
        done: list[Request] = []
        for req in [r for r in self.waiting if self._expired(r, now)]:
            self.waiting.remove(req)
            self._finish_abort(req, FinishReason.TIMEOUT, now)
            done.append(req)
        victims = [s for s in self.running.values()
                   if self._expired(s.request, now)]
        if victims:
            done.extend(self.drain())
            for seq in victims:
                if (seq.request.state is RequestState.FINISHED
                        or self.running.get(seq.slot) is not seq):
                    continue   # the drain finished it first
                self._finish_abort(seq.request, FinishReason.TIMEOUT, now)
                self._evict(seq)
                done.append(seq.request)
        return done

    @staticmethod
    def _expired(req: Request, now: float) -> bool:
        dl = req.sampling.deadline_s
        return (dl is not None and req.t_arrival >= 0
                and now - req.t_arrival > dl)

    _ABORT_COUNTER = {FinishReason.ABORTED: "aborts",
                      FinishReason.TIMEOUT: "timeouts",
                      FinishReason.SHED: "sheds"}

    def _finish_abort(self, req: Request, reason: FinishReason,
                      now: Optional[float] = None) -> None:
        """Complete the ABORTED-family lifecycle: cause in the event log,
        finished stamp + finish_reason, stats counter, trace instant."""
        if now is None:
            now = self._clock()
        req.mark(reason.value, now)
        req.finish(reason, self.step_idx, now)
        self.stats[self._ABORT_COUNTER[reason]] += 1
        self.stats["finished"] += 1
        self.tracer.instant("abort", req_id=req.req_id, reason=reason.value)

    def run(self) -> list[Request]:
        """Drive steps until every request has finished."""
        done: list[Request] = []
        while self.has_work():
            done.extend(self.step())
        return done

    def generate(self, prompts: jax.Array, gen: GenerationConfig) -> jax.Array:
        """Compat API: (B, S) prompts -> (B, max_new_tokens) tokens (rows
        that hit EOS early are zero-padded)."""
        B = prompts.shape[0]
        if gen.max_new_tokens < 1:
            return jnp.zeros((B, 0), jnp.int32)
        # distinct per-row seeds: identical prompt rows must sample
        # independent continuations, as the legacy batched draw did
        reqs = [self.add_request(
            prompts[b],
            SamplingParams(max_new_tokens=gen.max_new_tokens,
                           temperature=gen.temperature, eos_id=gen.eos_id,
                           seed=gen.seed + b))
            for b in range(B)]
        self.run()
        out = np.zeros((B, gen.max_new_tokens), np.int32)
        for b, r in enumerate(reqs):
            out[b, :len(r.output_tokens)] = r.output_tokens
        return jnp.asarray(out)

    # -- internals ---------------------------------------------------------

    def _plan(self) -> StepPlan:
        with self.tracer.span("plan", step=self.step_idx):
            return self.scheduler.plan_step(
                list(self.waiting), list(self.running.values()),
                self.pool_host, now=self._clock())

    def _admit(self, admissions: list[tuple[Request, int]]
               ) -> list[tuple[Sequence, int]]:
        """Move a FIFO prefix of the waiting queue into slots; their first
        chunks join this step's spans.  With prefix sharing the page table
        starts from the trie match — shared full pages by refcount, a
        partial/about-to-be-written page by COW fork (device page copies
        dispatched here, before the step that writes into the fork) — and
        the cursor starts at the matched length.  A resumed (preempted)
        request re-enters with its emitted tokens folded into the prefill
        target (re-matched against the trie, typically a cache hit on the
        pages it committed before eviction) and its saved PRNG stream."""
        if not admissions:
            return []
        with self.tracer.span("admit", step=self.step_idx,
                              n=len(admissions)):
            return self._admit_inner(admissions)

    def _admit_inner(self, admissions: list[tuple[Request, int]]
                     ) -> list[tuple[Sequence, int]]:
        spans: list[tuple[Sequence, int]] = []
        rows, temps, keys, wstarts = [], [], [], []
        cow_ops: list[tuple[int, int]] = []
        for req, chunk in admissions:
            # admissions come in priority-then-FIFO order, not necessarily a
            # queue prefix (priorities / sheds may skip entries) — remove by
            # identity
            try:
                self.waiting.remove(req)
            except ValueError:
                raise AssertionError(
                    f"admitted request {req.req_id} is not in the queue")
            req.state = RequestState.PREFILLING
            if req.admitted_step < 0:
                req.admitted_step = self.step_idx
            target = len(req.known_tokens)
            # the chunk's own pages are drawn in _dispatch, in scheduler
            # priority order (decodes -> residents -> admissions), so a
            # mid-step drift in what the trie still holds can only shrink
            # the lowest-priority spans, never starve a mandatory decode
            n_cow = 0
            if self.prefix_sharing:
                pages, matched, cow = self.pool_host.acquire_prefix(
                    req.req_id, req.known_tokens)
                chunk = min(chunk, target - matched)
                cow_ops.extend(cow)
                n_cow = len(cow)
                # read through the pool's counters — the pool also counts
                # adopt-in-place forks, which return no cow op
                self.stats["prefix_hit_tokens"] = \
                    self.pool_host.prefix_hit_tokens
                self.stats["cow_forks"] = self.pool_host.cow_forks
            else:
                # no trie, no drift: the exclusive path draws at admit
                pages, matched = self.pool_host.allocate(req.req_id,
                                                         chunk), 0
            req.num_computed_tokens = matched
            req.num_cached_tokens = matched
            now = self._clock()
            if req.t_admitted < 0:
                req.t_admitted = now
            req.mark("resumed" if req.num_preemptions else "admitted", now)
            if self.metrics_enabled:
                # queue-wait clock: arrival, or the last preemption — the
                # wait a victim re-pays is real scheduler latency
                self._h_queue_wait.observe((now - req.t_enqueued) * 1e3)
                self._h_cached.observe(matched)
                self._h_cow.observe(n_cow)
            slot = self._free_slots.pop()
            seq = Sequence(request=req, slot=slot, page_ids=pages,
                           prefill_target=target,
                           admit_order=next(self._admit_stamp),
                           t_admitted=now)
            self.running[slot] = seq
            self._pt_dirty.add(slot)
            spans.append((seq, chunk))
            rows.append(slot)
            temps.append(req.sampling.temperature)
            wstarts.append(matched)
            if req.resume_key is not None:
                keys.append(np.asarray(req.resume_key, np.uint32))
            else:
                keys.append(np.asarray(
                    jax.random.PRNGKey(req.sampling.seed), np.uint32))
        idx = np.asarray(rows)
        self._temp = self._temp.at[idx].set(np.asarray(temps, np.float32))
        self._wstart = self._wstart.at[idx].set(
            np.asarray(wstarts, np.int32))
        self._keys = self._keys.at[idx].set(np.stack(keys))
        if cow_ops:
            # whole-page device copies; rows past the fork point are stale
            # source data, masked by causality until the forking sequence
            # overwrites them with its own span writes
            n = _bucket(len(cow_ops))
            src = np.full((n,), SINK_PAGE, np.int32)  # pad: sink onto itself
            dst = np.full((n,), SINK_PAGE, np.int32)
            for i, (s, d) in enumerate(cow_ops):
                src[i], dst[i] = s, d
            self.pool = _cow_copy_jit(self.pool, jnp.asarray(src),
                                      jnp.asarray(dst))
        if self.metrics_enabled:
            self._g_queue.set(len(self.waiting))
        return spans

    def _dispatch(self, spans: list[tuple[Sequence, int]]) -> None:
        """Grow page tables to cover every span, build the (slot, span)
        batch, and dispatch the jitted mixed step."""
        with self.tracer.span("dispatch", step=self.step_idx,
                              spans=len(spans)) as sp:
            bucket, prefill_rows, decode_rows = self._dispatch_inner(spans)
            sp.note(bucket=bucket, prefill_rows=prefill_rows,
                    decode_rows=decode_rows)

    def _dispatch_inner(self, spans: list[tuple[Sequence, int]]
                        ) -> tuple[int, int, int]:
        """Returns the step's span bucket and its prefill and decode row
        counts."""
        # injected dispatch failures fire HERE, before any host bookkeeping
        # (cursor advances, page draws) — the recovery path in _step_inner
        # assumes a failed dispatch mutated nothing
        if self.faults is not None:
            self.faults.on_dispatch(self)
        B = self.max_slots
        Sb = _bucket(max(n for _, n in spans))
        self.last_span_bucket = Sb  # instrumentation: which jit variant ran
        chunk_tok = np.zeros((B, Sb), np.int32)
        start = np.zeros((B,), np.int32)
        span = np.zeros((B,), np.int32)          # 0 = inert row (sink writes)
        use_dev = np.zeros((B,), bool)
        sample = np.zeros((B,), bool)
        harvest: list[tuple[int, Sequence]] = []
        n_dec, dec_ctx, prefill_toks, n_rows = 0, 0, 0, 0

        for seq, n in spans:
            req = seq.request
            nc = seq.num_computed
            if req.state is not RequestState.RUNNING:
                # prefill chunk: absorb planning drift (a trie eviction
                # between plan and execution can shift a fresh admission's
                # match by a fraction of a page) by shrinking the span to
                # the pages actually on hand; 0 stalls the row this step
                cover = (len(seq.page_ids) * self.page_size - nc
                         + self.pool_host.free_pages * self.page_size)
                n = min(n, max(cover, 0))
                if n <= 0:
                    continue
            new = self.pool_host.extend(req.req_id, nc + n)
            if new:
                seq.page_ids.extend(new)
                self._pt_dirty.add(seq.slot)
            # write confinement: the span [nc, nc+n) must land only in pages
            # this sequence exclusively owns (refcount 1, uncommitted rows)
            self.pool_host.assert_writable(req.req_id, nc, nc + n)
            s = seq.slot
            start[s] = nc
            span[s] = n
            if req.state is RequestState.RUNNING:   # decode: device token
                use_dev[s] = True
                sample[s] = True
                n_dec += 1
                dec_ctx += nc
                self.stats["decode_tokens"] += 1
                if self.metrics_enabled:
                    self.dispatch_log.append(
                        (self.step_idx, req.req_id, "decode", 1))
            else:                                    # prefill chunk
                toks = req.known_tokens[nc:nc + n]
                chunk_tok[s, :n] = toks
                reaches_end = nc + n >= seq.prefill_target
                sample[s] = reaches_end
                prefill_toks += n
                self.stats["prefill_tokens"] += n
                if self.metrics_enabled:
                    self.dispatch_log.append(
                        (self.step_idx, req.req_id, "prefill", n))
                if reaches_end:
                    req.state = RequestState.RUNNING
                if self.prefix_sharing:
                    # every full page the cursor just crossed (and, at the
                    # end of prefill, the partial tail) becomes shareable —
                    # the device write for these rows is already enqueued
                    # ahead of any future forward that could read them
                    self.pool_host.commit_prefix(req.req_id,
                                                 req.known_tokens, nc + n)
            req.num_computed_tokens = nc + n
            self.pool_host.advance(req.req_id, n)
            n_rows += 1
            if sample[s]:
                harvest.append((s, seq))

        if self._pt_dirty:
            rows = np.full((len(self._pt_dirty), self.max_pages_per_seq),
                           SINK_PAGE, np.int32)
            idx = np.asarray(sorted(self._pt_dirty))
            for i, s in enumerate(idx):
                ids = self.running[s].page_ids
                rows[i, :len(ids)] = ids
            self._pt = self._pt.at[idx].set(rows)
            self._pt_dirty.clear()

        lat, nrg = self.scheduler.step_cost(
            n_dec, (dec_ctx / n_dec) if n_dec else 0.0, prefill_toks)
        self.stats["sim_latency_ns"] += lat
        self.stats["sim_energy_nj"] += nrg
        self.stats["mixed_steps"] += 1

        # kernel-dispatch observability: re-derive the SAME cached decision
        # the traced step took for this span bucket (kernels.ops holds the
        # one decision function), so the tp>1 kernel win — and any silent
        # VMEM-spill regression when a bucket grows — shows up in stats
        decision = self._kernel_decision(Sb)
        if decision == "kernel":
            self.stats["kernel_dispatches"] += 1
        else:
            self.stats["dense_fallbacks"] += 1
            self.stats[f"dense_fallback_{decision}"] += 1

        if self.metrics_enabled or self.tracer.enabled:
            # per-iteration batch composition + pool pressure.  stats() is a
            # full pool scan, once per step: thousands of pages on a chip
            # (3,770 in the benchmark's one-v5e pool), off by default with
            # metrics=False.
            ps = self.pool_host.stats()
            if self.metrics_enabled:
                self._h_batch.observe(n_rows)
                self._h_chunk.observe(prefill_toks)
                self._g_free.set(ps.free_pages)
                self._g_shared.set(ps.shared_pages)
                self._g_cached.set(ps.cached_pages)
                self._g_held.set(ps.unique_pages)
                self._g_evict.set(ps.cache_evictions)
            if self.tracer.enabled:
                self.tracer.counter(
                    "pool_pages", free=ps.free_pages, shared=ps.shared_pages,
                    cached=ps.cached_pages)

        (self.pool, sampled, self._tok, self._keys) = self._mixed(
            self.params, self.pool, jnp.asarray(chunk_tok), self._tok,
            jnp.asarray(use_dev), jnp.asarray(start), jnp.asarray(span),
            self._pt, self._wstart, jnp.asarray(sample), self._temp,
            self._keys)
        self._pending.append({"sampled": sampled, "slots": harvest,
                              "step": self.step_idx})
        return Sb, n_rows - n_dec, n_dec

    def _kernel_decision(self, span: int) -> str:
        """The kernel-vs-dense decision the traced mixed step took for this
        span bucket — ``kernels.ops.paged_dispatch`` with exactly the
        arguments ``models.layers._paged_attend`` derives from the traced
        shapes, so the counters can never drift from the compiled path.
        (Both calls hit the same ``lru_cache`` entry; this is a dict lookup
        per step, not a recomputation.)"""
        from repro.core.quant import KV_DTYPE_BYTES
        from repro.kernels.ops import paged_dispatch

        cfg = self.cfg
        kv_shard = (self.tp if self.tp > 1
                    and cfg.n_kv_heads % self.tp == 0
                    and cfg.n_heads % self.tp == 0 else 1)
        return paged_dispatch(
            span, cfg.n_heads, cfg.hd, self.page_size, cfg.n_kv_heads,
            KV_DTYPE_BYTES[self.kv_dtype],
            quantized=self.kv_dtype == "int8", tp=self.tp,
            kv_shard=kv_shard, paged_kernel=cfg.paged_kernel,
            softcap=cfg.logit_softcap is not None)

    def _harvest(self, entry: dict) -> list[Request]:
        step = entry.get("step", -1)
        with self.tracer.span("harvest", step=step):
            with self.tracer.span("sync", step=step):
                sampled = np.asarray(entry["sampled"])  # blocks on device
            # token timestamps are taken HERE, after the device sync: with
            # the one-step harvest lag a dispatch-time stamp would antedate
            # the token (see request.py docstring)
            now = self._clock()
            finished = []
            for slot, seq in entry["slots"]:
                req = seq.request
                if req.state is not RequestState.RUNNING:
                    continue  # finished by an earlier harvest, or preempted
                if self.running.get(slot) is not seq:
                    continue  # slot was recycled after an eviction
                self._emit(seq, int(sampled[slot]), now)
                if req.state is RequestState.FINISHED:
                    finished.append(req)
            return finished

    def _emit(self, seq: Sequence, token: int,
              now: Optional[float] = None) -> None:
        req = seq.request
        req.emit(token)
        self.stats["tokens_out"] += 1
        if now is None:
            now = self._clock()
        if len(req.output_tokens) == 1:
            req.t_first_token = now
            req.mark("first_token", now)
            if self.metrics_enabled:
                self._h_ttft.observe((now - req.t_arrival) * 1e3)
        elif self.metrics_enabled and req.t_last_token > 0:
            self._h_itl.observe((now - req.t_last_token) * 1e3)
        req.t_last_token = now
        sp = req.sampling
        if sp.eos_id is not None and token == sp.eos_id:
            req.finish(FinishReason.EOS, self.step_idx, now)
        elif len(req.output_tokens) >= sp.max_new_tokens:
            req.finish(FinishReason.LENGTH, self.step_idx, now)
        if req.state is RequestState.FINISHED:
            self.stats["finished"] += 1
            if self.metrics_enabled:
                self._h_e2e.observe((now - req.t_arrival) * 1e3)
            self._evict(seq)

    def _evict(self, seq: Sequence) -> None:
        slot = seq.slot
        self.pool_host.free(seq.req_id)
        self.running.pop(slot)
        self._free_slots.append(slot)
        self._pt_dirty.discard(slot)
        self._pt = self._pt.at[slot].set(SINK_PAGE)

    def _preempt(self, seq: Sequence) -> None:
        """Evict a PREFILLING/RUNNING sequence back to WAITING: pages freed,
        cursor reset (KV is gone — recompute on resume), emitted tokens and
        the per-request PRNG stream kept.  The victim rejoins at the FRONT
        of the queue so FIFO admission resumes it as soon as pages free up."""
        req = seq.request
        # the harvest drain ran before any preemption, so _keys[slot] is the
        # settled post-draw carry — sampling resumes mid-stream on re-admit
        req.resume_key = np.asarray(self._keys[seq.slot])
        self.pool_host.free(req.req_id)
        self.running.pop(seq.slot)
        self._free_slots.append(seq.slot)
        self._pt_dirty.discard(seq.slot)
        self._pt = self._pt.at[seq.slot].set(SINK_PAGE)
        req.num_computed_tokens = 0
        req.state = RequestState.WAITING
        req.num_preemptions += 1
        # queue-wait clock restarts (this also resets the shed budget — a
        # victim gets a fresh max_queue_wait_s, it already earned its slot)
        req.t_enqueued = req.mark("preempted", self._clock())
        self.stats["preemptions"] += 1
        self.tracer.instant("preempt", req_id=req.req_id)
        self.waiting.appendleft(req)

    # -- snapshot / restore ------------------------------------------------

    def snapshot(self, include_kv: bool = True) -> dict:
        """Serialize the complete serving state (queues, cursors, page
        tables, prefix trie, slot arrays, device KV) after draining the
        in-flight dispatch chain.  ``include_kv=False`` captures only host
        state — restore then falls back to recompute-on-resume."""
        from repro.serving.snapshot import snapshot_engine

        return snapshot_engine(self, include_kv=include_kv)

    def save_snapshot(self, directory, include_kv: bool = True) -> dict:
        """``snapshot()`` persisted through ``checkpoint/store.py`` (atomic
        rename, per-leaf CRC32).  Returns the in-memory snapshot."""
        from repro.serving.snapshot import save_snapshot

        snap = self.snapshot(include_kv=include_kv)
        save_snapshot(directory, snap)
        return snap

    @classmethod
    def restore(cls, snap: dict, cfg: ModelConfig, params,
                **engine_kw) -> "ContinuousBatchingEngine":
        """Rebuild an engine from a ``snapshot()`` dict — see
        ``serving/snapshot.py`` for the recovery contract."""
        from repro.serving.snapshot import restore_engine

        return restore_engine(snap, cfg, params, **engine_kw)

    @classmethod
    def restore_latest(cls, directory, cfg: ModelConfig, params,
                       **engine_kw) -> "ContinuousBatchingEngine":
        """Restore from the newest on-disk snapshot under ``directory``."""
        from repro.serving.snapshot import load_snapshot, restore_engine

        return restore_engine(load_snapshot(directory, cfg), cfg, params,
                              **engine_kw)


class ServeEngine:
    """Legacy single-batch engine, kept as a compat shim.

    Fixed relative to the seed: (1) attn stacks prefill the whole prompt
    block in ONE forward through the ring cache instead of S sequential
    decode steps; (2) the decode loop never syncs on the host — all
    ``max_new_tokens`` steps are dispatched back-to-back and EOS trimming
    happens once at the end on a single fetched array, reproducing the old
    early-break output exactly (the seed also kept decoding rows that had
    already hit EOS until ALL rows were done).
    """

    def __init__(self, cfg: ModelConfig, params, max_len: int = 512):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self._decode = functools.partial(_ring_decode_jit, cfg=cfg)
        self._prefill = None
        if cfg.layer_kind == "attn":
            self._prefill = functools.partial(_ring_prefill_jit, cfg=cfg)

    def _sample(self, logits, key, temperature):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / temperature, axis=-1
                                      ).astype(jnp.int32)

    def generate(self, prompts: jax.Array, gen: GenerationConfig):
        """prompts: (B, S_prompt) int32 -> (B, <=max_new_tokens) int32."""
        B, S = prompts.shape
        if gen.max_new_tokens < 1:
            return jnp.zeros((B, 0), jnp.int32)
        cache = T.init_decode_cache(self.cfg, B, self.max_len)
        key = jax.random.PRNGKey(gen.seed)
        if self._prefill is not None:
            logits, cache = self._prefill(self.params, prompts, cache)
        else:  # SSM/hybrid states advance token-by-token
            logits = None
            for t in range(S):
                logits, cache = self._decode(self.params, prompts[:, t], cache)
        tok = self._sample(logits, key, gen.temperature)
        outs = [tok]
        for _ in range(gen.max_new_tokens - 1):
            logits, cache = self._decode(self.params, tok, cache)
            key, sub = jax.random.split(key)
            tok = self._sample(logits, sub, gen.temperature)
            outs.append(tok)
        out = jnp.stack(outs, axis=1)
        if gen.eos_id is not None:  # single host fetch, then trim
            arr = np.asarray(out)
            done = np.cumsum(arr == gen.eos_id, axis=1) > 0
            cols = done.all(axis=0)
            if cols.any():
                out = out[:, :int(np.argmax(cols)) + 1]
        return out


__all__ = ["ContinuousBatchingEngine", "ServeEngine", "GenerationConfig"]
