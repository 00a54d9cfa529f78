"""Serving runtime: unified chunked-prefill + decode iterations over a
refcounted, prefix-sharing paged KV cache, with CIM-cost-aware scheduling,
copy-on-write page forks, preemption, and tensor parallelism over a
``("data", "model")`` device mesh.

Every engine iteration is ONE mixed forward: each admitted sequence
contributes a variable-length token span — a prefill chunk, the tail of a
chunked prompt, or a single decode token — so long prompts never
head-of-line-block the decode batch and there is no separate prefill pass.
That stays true under tensor parallelism: the TP engine compiles the same
mixed step once per mesh (GSPMD partitions it from the parameter
shardings and ``sharding/api.logical`` activation constraints) and every
iteration is still one jitted dispatch.

Tensor-parallel ownership contract (``DeviceKV``, the device half of the
KV pool — the paper's per-array weight/KV residency, software edition):

  * REPLICATED ON HOST: page tables, the refcounted prefix trie, free
    lists, cursors.  The host pool plans in LOGICAL pages and never sees a
    shard, so scheduling, admission, preemption, prefix matching and COW
    planning are global decisions, byte-identical at every ``tp``.
  * SHARDED ON DEVICE: page buffers split on their KV-head axis over the
    mesh's "model" axis (per-(page, kv_head) int8 scale rows ride with
    their heads); Monarch/attention weights split by the
    ``sharding/params.py`` suffix rules (stage-1 block-rows column-
    parallel, stage-2 contraction row-parallel -> one all-reduce, the
    software twin of the paper's inter-array reduction bus).  A KV-head
    count "model" does not divide leaves the pool replicated
    (``kv_shard == 1``) — GQA-correct, never uneven.
  * WRITES STAY LOCAL: span writes and COW copies scatter on the page
    axis, which is never sharded — every shard performs the same
    page-granular operation on its local KV-head slice, no cross-shard
    traffic.  The same locality is what lets the Pallas span kernel run
    shard_mapped at ``tp > 1``: each shard executes the identical kernel
    grid over its local KV-head slice of the page buffers and scale rows
    (``kernels/paged.py::paged_attention_span_sharded``), with the honest
    per-shard VMEM fit through ``paged_span_fits(n_shards=kv_shard)``.
    Only GQA-replicated pools (``kv_shard == 1``) fall back to the dense
    gather, which partitions on the query-head axis.
  * SNAPSHOTS ARE MESH-INDEPENDENT: ``DeviceKV.export`` gathers shards,
    ``DeviceKV.load`` re-shards onto the restoring mesh, and
    ``DeviceKV.check_shards`` is the per-shard recovery invariant.
  * ``mesh=None`` (the default) bypasses all of it — the single-device
    engine path is bit-identical to the pre-mesh code, and ``tp>1``
    greedy decoding is token-identical to ``tp=1``.

Per-shard page budgets: ``pool_bytes`` is the budget of ONE shard's
memory, so the engine sizes the pool by ``shard_page_bytes`` (a page's
bytes divided by ``kv_shard``) — at ``tp=N`` the same budget holds ~N×
the logical pages.  Both cost models take ``tp=`` and price the split
(weights /tp, KV /kv_shard) plus the all-reduce term
(``scheduler.tp_allreduce_bytes_per_token``).

Lifecycle:  WAITING -> PREFILLING -> RUNNING -> FINISHED, with preemption
sending PREFILLING/RUNNING back to WAITING.  A PREFILLING request's
``num_computed_tokens`` cursor walks its known tokens in scheduler-sized
chunks; KV pages are allocated incrementally as the cursor advances (no
conservative prompt + max_new reservation).  The chunk that reaches the end
of the known tokens samples the next token on device, and the request
decodes one token per step from then on.

Ownership contract (refcounts / prefix trie / copy-on-write):  on a CIM
system the whole model is resident, so KV capacity — not weights — is the
scarce on-chip resource, and recomputing shared prompt prefixes burns
exactly the FLOPs block-diagonal sparsity eliminated.  The pool therefore
shares pages across sequences:

  * WHO MAY WRITE A PAGE: only the single sequence holding it with
    refcount 1, and only at positions at or beyond its committed rows.
    Shared pages are immutable history.  This is enforced twice — host-side
    by ``PagedKVPool.assert_writable`` on every scheduled span, device-side
    by a write-mask derived from the fork point (``write_start`` in
    ``paged_mixed_step``) that redirects any write below it to the sink
    page.
  * WHEN FORKS HAPPEN: admission matches the request's known tokens
    against the trie.  Full-page hits are refcount bumps (zero new pages,
    zero prefill tokens).  The match is capped one token short of the
    prompt (the sampler needs fresh logits), so a fully-cached prompt — or
    a hit ending inside a partially-committed page — triggers a
    copy-on-write fork: one private page is drawn, the shared page is
    copied on device (``models.transformer.cow_copy_pages``, dispatched
    before the fork's first forward), and the cursor starts at the matched
    length.  Decode writes then land only in the private fork/tail pages.
  * PREEMPTION OF SHARED PAGES: evicting a victim releases its refcounts;
    pages other sequences (or the trie) still hold survive, so a victim
    yields only its exclusive pages (``release_yield`` for one victim; the
    scheduler's preemption loop additionally credits pages shared only
    among the victims chosen so far, exactly once).  On
    re-admission the victim RE-MATCHES ``prompt + emitted`` against the
    trie — typically hitting the very pages it committed before eviction —
    and recomputes only the unmatched tail.  Greedy output is
    token-identical through preemption, sharing on or off.
  * LIFETIME: committed pages outlive their sequence; ``free`` decrements
    and a page is only returned to the free list when neither a sequence
    nor the trie holds it.  Cached-only pages are reclaimed LRU (leaves
    first) when allocation needs them.

Telemetry:  the engine is observable end to end, with zero dependencies.
``engine.stats`` is a typed ``EngineStats`` view over a per-engine
``MetricsRegistry`` (counters / gauges / fixed-bucket histograms with
Prometheus-style percentile estimation; ``registry.snapshot()`` is a
JSON-ready nested dict).  Every ``Request`` carries wall-clock lifecycle
stamps (arrival -> admitted -> first token -> finished, plus an
append-only event log recording preemptions and resumes) from which TTFT,
inter-token latency, queue wait and end-to-end latency histograms are
derived — token stamps are taken at device-sync HARVEST time, never at
dispatch, because the engine's one-step harvest lag would otherwise
antedate them.  ``ContinuousBatchingEngine(..., trace="out.json")``
brackets each iteration's phases (plan / admit / dispatch / sync /
harvest) with Chrome trace-event spans — ``engine.tracer.save()`` writes
Perfetto-loadable JSON; the same spans also land in an active
``jax.profiler`` trace as host annotations, and the mixed step's layers
carry the ``jax.named_scope`` names (``linear``, ``attention``,
``unembed``, ``embed``, ``norm``, ``sample``) that ``tracing.py`` lists as
the contract the benchmark reads — and a ``Calibration`` pairs each step's
cost-model prediction (``sim_latency_ns``) with measured wall time,
fitting the scale factor ``benchmarks/serve_throughput.py`` publishes in
``BENCH_serving.json``'s ``telemetry`` section.  ``metrics=False`` keeps
only the raw counters; with tracing off every span hook is a shared no-op
singleton.

Fault tolerance / recovery contract:  serving keeps running — and keeps
its outputs exact — through client aborts, SLO expiry, overload, and
process death.  The contract has four legs:

  * DEADLINES & CANCELLATION: ``SamplingParams.deadline_s`` bounds a
    request's total wall-clock lifetime (a per-step sweep drives expired
    requests — queued or mid-generation — to FINISHED/TIMEOUT) and
    ``engine.cancel(req_id)`` aborts at any lifecycle stage.  Teardown of
    a resident sequence ALWAYS drains the in-flight dispatch chain first:
    the engine's one-step harvest lag means a cancelled slot could
    otherwise be resurrected (or written into) by a step dispatched
    before the cancel landed.  Pages are released refcount-correctly —
    shared prefix pages survive with their other holders.
  * OVERLOAD SHEDDING: ``max_queue_wait_s`` is the admission-control
    budget — a WAITING request past it that the scheduler still cannot
    admit is SHED (it never held pages, so shedding is pure queue
    surgery), and under page pressure the scheduler first DEGRADES
    prefill chunk sizes (``SchedulerConfig.degrade_free_frac``) before
    resorting to preemption.  ``priority`` orders admission and
    preemption; ties keep FIFO.
  * SNAPSHOT/RESTORE: ``engine.snapshot()`` /
    ``ContinuousBatchingEngine.restore()`` round-trip the complete
    serving state — queues, cursors, page tables, prefix trie, device KV,
    per-slot PRNG streams — through ``checkpoint/store.py`` (atomic
    rename, per-leaf CRC32).  A full restore resumes mid-flight requests
    token-identically (greedy AND sampled); a degraded restore (no KV)
    falls back to the preemption contract: everyone re-enters WAITING and
    recomputes, still token-identical.  ``ft.coordinator.EngineSupervisor``
    watches the engine's per-step heartbeat and rebuilds a quiet engine
    from its last published snapshot.
  * FAULT INJECTION: ``serving/faults.py`` is a seeded, schedulable chaos
    source (pool exhaustion, dispatch failure, simulated crashes around
    the harvest, clock skew) the engine hosts via ``fault_injector=``;
    ``assert_recovery_invariants`` is the shared post-fault oracle (pool
    refcounts exact, no leaked pages, slot accounting exact) used by the
    chaos tests and the ``serve_throughput.py`` robustness sweep.

Fleet-level recovery contract (``replicas.py`` + ``ft.coordinator``):
above the single engine, a ``ReplicatedEngine`` keeps a health state per
replica (HEALTHY / DEGRADED / DRAINING / DOWN) driven by a
``FleetSupervisor`` — per-replica heartbeat ranks in one shared registry,
a fleet-median straggler monitor, one published snapshot per rank — plus
step-exception capture: a replica whose ``step()`` raises (or goes
heartbeat-silent) is marked DOWN and failed over instead of poisoning the
router loop, and ``route()`` never selects a non-HEALTHY replica.

  * WHAT FAILOVER PRESERVES: with a published snapshot, the slot restores
    in place under a fresh rank, token-identical per the snapshot
    contract for everything the snapshot holds; requests the router
    already reported finished are reconciled away (never re-served).
  * WHAT MIGRATION RECOMPUTES: without a snapshot, orphaned requests
    (prompt, emitted tokens, budgets, priority) readmit on survivors as
    WAITING — recompute-on-resume pays only the KV work again, and
    sampled requests replay their PRNG carry host-side from the seed, so
    greedy AND sampled outputs stay token-identical.
  * WHAT QUARANTINE DROPS: a request whose replica dies
    ``max_request_retries`` times under it is poison — it finishes
    ABORTED (``router.quarantined``) instead of taking another replica
    down.  Nothing else is ever dropped: 100% of non-poisoned requests
    finish.
  * ELASTICITY: ``drain_replica`` / ``scale_to`` resize the fleet
    (migrate-and-detach, or fresh same-geometry engines), and fleet
    snapshots (format v2) record health + retry state so restore
    reproduces a degraded fleet exactly.  ``assert_fleet_invariants`` is
    the fleet-level oracle: every survivor passes the single-engine
    invariants and the owner table references only live requests.

Module map:
  request.py   — ``Request``/``Sequence`` lifecycle, the
                 ``num_computed_tokens`` cursor (starts at the matched
                 prefix length), ``num_cached_tokens``, per-request
                 ``SamplingParams``, streaming ``on_token`` callbacks,
                 wall-clock lifecycle timestamps (``ttft`` /
                 ``queue_wait`` / ``e2e_latency``).
  kv_pool.py   — ``PagedKVPool``: refcounted pages, per-sequence page
                 tables, the radix/prefix trie over token IDs
                 (``match_prefix`` / ``acquire_prefix`` /
                 ``commit_prefix``), COW forks, LRU reclaim, write
                 confinement, and sharing-aware ``PoolStats``
                 (shared/unique/cached pages, prefix hit tokens + rate,
                 high-water ``peak_pages``/``peak_bytes``, LRU
                 ``cache_evictions``, per-shard ``kv_shard`` /
                 ``shard_page_bytes``).  Host-side twin of the device
                 pool in ``models.transformer.init_paged_pool``.
  device_kv.py — ``DeviceKV``: owner of the device-side pool pytree and
                 its mesh placement (see the TP ownership contract
                 above); ``export`` / ``load`` / ``check_shards``.
  scheduler.py — ``IterationScheduler.plan_step``: packs prefill chunks
                 around the in-flight decodes each step under
                 slot/page/token/latency budgets; admission budgets count
                 only UNIQUE new pages (trie hits are free) and the
                 pluggable ``CostModel`` prices cached tokens at ~zero
                 (``prefill_ns(n, cached_tokens=...)``) — ``HBMCostModel``
                 (weight-streaming roofline) and ``CIMCostModel`` (priced
                 by the paper's CIM simulator).
  replicas.py  — ``ReplicatedEngine``: R independent engine replicas
                 behind a shared admission point with prefix-trie
                 affinity routing (``match_prefix`` scored per replica,
                 least-loaded fallback, ``routing="round_robin"``
                 baseline), per-replica health + failover/migration/
                 quarantine, elastic ``drain_replica``/``scale_to``,
                 fanned metrics, fleet snapshots — see its module
                 docstring for the router/affinity and fault-tolerance
                 contracts.
  engine.py    — ``ContinuousBatchingEngine``: ONE jitted mixed step over
                 (slot, span) with on-device sampling, lagged token
                 harvest, trie lookup at ``add_request``, prefix acquire +
                 COW dispatch at admission, incremental page allocation,
                 page commits as the cursor crosses boundaries, and the
                 preemption/resume machinery; ``prefix_sharing=False``
                 restores exclusive ownership.  Plus the legacy
                 ``ServeEngine`` compat shim.
  metrics.py   — dependency-free ``MetricsRegistry`` (Counter / Gauge /
                 Histogram), the dict-compatible ``EngineStats``, and
                 ``Calibration`` (predicted-vs-measured cost-model fit).
  faults.py    — ``FaultInjector`` (seeded, schedulable chaos),
                 ``DispatchFailure`` / ``SimulatedCrash``, and the
                 ``assert_recovery_invariants`` post-fault oracle.
  snapshot.py  — ``snapshot_engine`` / ``restore_engine`` and the on-disk
                 round trip (``save_snapshot`` / ``load_snapshot``) via
                 ``checkpoint/store.py``.
  tracing.py   — ``ChromeTracer`` Chrome trace-event spans (Perfetto,
                 and profiler annotations when enabled), the no-op
                 ``NULL_TRACER``, ``validate_trace`` (the machine-checkable
                 "loads in Perfetto"), and the contract of the mixed
                 step's named scopes.

The span-aware Pallas paged-gather attention kernel lives in
``kernels/paged.py`` (oracles: ``kernels/ref.py::paged_attention_span_ref``
/ ``paged_attention_ref``); enable it with
``ContinuousBatchingEngine(..., use_paged_kernel=True)``.  It runs at any
``tp``: single-device as a plain pallas_call, under a >1 "model" axis
shard_mapped per KV-head slice (bitwise-identical outputs either way).
The kernel-vs-dense decision is ``kernels/ops.py::paged_dispatch`` —
consulted at trace time by ``models/layers.py`` and re-derived per step by
the engine, which counts it in ``stats`` (``kernel_dispatches``,
``dense_fallbacks`` and ``dense_fallback_<reason>``).

KV pages are stored at the engine's ``kv_dtype`` ("fp32" | "bf16" |
"int8"; None = model dtype).  int8 pools quantize fresh spans on device
before the page write — one fp32 scale per (page, kv_head), K and V
independent (``core.quant``) — dequantize in-kernel on read, copy scales
with their pages on COW forks, and under a fixed ``pool_bytes`` budget
hold ~4x the fp32 page count: the capacity that turns PR 4's page sharing
into fewer preemptions.  ``PoolStats`` reports the physical bytes; both
cost models price the KV stream at the stored width.
"""

from repro.serving.device_kv import (DeviceKV,  # noqa: F401
                                     kv_shard_size, pool_shardings)
from repro.serving.engine import (ContinuousBatchingEngine,  # noqa: F401
                                  GenerationConfig, ServeEngine)
from repro.serving.faults import (DispatchFailure,  # noqa: F401
                                  FaultInjector, InjectedFault,
                                  SimulatedCrash,
                                  assert_fleet_invariants,
                                  assert_recovery_invariants)
from repro.serving.kv_pool import (PagedKVPool, PoolOOM,  # noqa: F401
                                   PoolStats, PrefixMatch)
from repro.serving.metrics import (Calibration, Counter,  # noqa: F401
                                   EngineStats, Gauge, Histogram,
                                   MetricsRegistry, render_report)
from repro.serving.replicas import (ReplicaHealth,  # noqa: F401
                                    ReplicatedEngine, ROUTING_POLICIES)
from repro.serving.request import (FinishReason, Request,  # noqa: F401
                                   RequestState, SamplingParams, Sequence)
from repro.serving.scheduler import (CIMCostModel, CostModel,  # noqa: F401
                                     HBMCostModel, IterationScheduler,
                                     SchedulerConfig, StepPlan)
from repro.serving.tracing import (NULL_TRACER, ChromeTracer,  # noqa: F401
                                   NullTracer, load_trace, validate_trace)
