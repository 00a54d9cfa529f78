"""Tests for the continuous-batching serving runtime: refcounted paged-pool
invariants (property-tested, with prefix match/fork/commit/release
interleavings), prefix-trie sharing + copy-on-write semantics, write
confinement (host assert + device write-mask), the chunk-packing scheduler
with cache-hit-aware admission + preemption planning, the span-aware paged
attention kernel vs its oracle, and token-identical equivalence between the
unified mixed-step engine and the single-request path — across chunk sizes,
with prefix sharing on and off, and through preemption."""

import dataclasses

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st  # optional dep: skips when absent

import jax
import jax.numpy as jnp

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serving import (ContinuousBatchingEngine, GenerationConfig,
                           HBMCostModel, IterationScheduler, PagedKVPool,
                           PoolOOM, Request, RequestState, SamplingParams,
                           SchedulerConfig, Sequence, ServeEngine)
from repro.serving.kv_pool import SINK_PAGE

CFG = ModelConfig(name="t", d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
                  d_ff=128, vocab=256, dtype="float32")


@pytest.fixture(scope="module")
def params():
    return T.init_params(jax.random.PRNGKey(0), CFG)


# ---------------------------------------------------------------------------
# paged KV pool
# ---------------------------------------------------------------------------


def test_pool_alloc_free_roundtrip():
    pool = PagedKVPool(n_pages=9, page_size=4)
    t1 = pool.allocate(1, 10)   # 3 pages
    t2 = pool.allocate(2, 4)    # 1 page
    assert len(t1) == 3 and len(t2) == 1
    assert SINK_PAGE not in t1 + t2
    assert pool.free_pages == 8 - 4
    pool.check_invariants()
    pool.free(1)
    assert pool.free_pages == 7
    pool.check_invariants()
    t3 = pool.allocate(3, 28)   # 7 pages: exactly drains the pool
    assert pool.free_pages == 0
    assert set(t3).isdisjoint(t2)
    pool.check_invariants()


def test_pool_oom_and_double_alloc():
    pool = PagedKVPool(n_pages=5, page_size=4)
    pool.allocate(1, 12)
    with pytest.raises(PoolOOM):
        pool.allocate(2, 8)   # 2 pages needed, 1 free
    with pytest.raises(ValueError):
        pool.allocate(1, 4)   # seq 1 already allocated
    pool.check_invariants()


def test_pool_extend_and_utilization():
    pool = PagedKVPool(n_pages=9, page_size=4)
    pool.allocate(1, 4)
    pool.advance(1, 2)
    assert pool.stats().utilization == pytest.approx(0.5)
    new = pool.extend(1, 8)
    assert len(new) == 1 and len(pool.page_table(1)) == 2
    assert pool.extend(1, 6) == []  # already covered
    pool.check_invariants()


def test_pool_free_unknown_seq_is_clean_error():
    pool = PagedKVPool(n_pages=5, page_size=4)
    with pytest.raises(KeyError, match="unknown sequence 7"):
        pool.free(7)
    pool.allocate(1, 4)
    pool.free(1)
    with pytest.raises(KeyError):
        pool.free(1)   # double free is an error, not a silent no-op
    pool.check_invariants()


def test_prefix_sharing_full_page_hit_is_zero_new_pages():
    """A second request with an identical committed prompt acquires the same
    physical pages by refcount — zero pages drawn, zero tokens to compute
    (bar the final token, which is never matched)."""
    pool = PagedKVPool(n_pages=17, page_size=4)
    toks = list(range(12))             # exactly 3 pages
    pool.allocate(1, 12)
    pool.commit_prefix(1, toks, 12)
    free0 = pool.free_pages
    pages, matched, cow = pool.acquire_prefix(2, toks + [99])
    assert matched == 12 and not cow
    assert pages == pool.page_table(1)
    assert pool.free_pages == free0    # refcount bumps only
    assert all(pool.refcount(p) == 2 for p in pages)
    pool.check_invariants()


def test_prefix_sharing_cow_forks_fully_cached_prompt():
    """An identical page-aligned prompt is FULLY cached, but its last token
    must be recomputed for logits — the last matched page forks COW into a
    private page instead of being shared."""
    pool = PagedKVPool(n_pages=17, page_size=4)
    toks = list(range(8))
    pool.allocate(1, 8)
    pool.commit_prefix(1, toks, 8)
    pages, matched, cow = pool.acquire_prefix(2, toks)
    assert matched == 7                      # cap: one token recomputed
    assert len(cow) == 1
    src, dst = cow[0]
    assert src == pool.page_table(1)[1]      # forked FROM the shared page
    assert dst == pages[-1] and dst not in pool.page_table(1)
    assert pool.refcount(src) == 1 and pool.refcount(dst) == 1
    assert pool.refcount(pages[0]) == 2      # first page genuinely shared
    pool.check_invariants()


def test_prefix_sharing_partial_page_cow():
    """A committed prompt tail shorter than one page is matched through a
    COW fork of the partial page (rows beyond the commit are not matched)."""
    pool = PagedKVPool(n_pages=17, page_size=4)
    toks = list(range(10))             # 2 full pages + 2-row partial
    pool.allocate(1, 10)
    pool.commit_prefix(1, toks, 10)
    m = pool.match_prefix(toks + [77, 78])
    assert m.n_tokens == 10 and m.cow == (pool.page_table(1)[2], 2)
    pages, matched, cow = pool.acquire_prefix(2, toks + [77, 78])
    assert matched == 10 and len(cow) == 1
    assert pool.refcount(pool.page_table(1)[2]) == 1  # partial NOT shared
    pool.check_invariants()
    # diverging mid-partial: only the common prefix of the tail matches
    m2 = pool.match_prefix(toks[:9] + [55, 66])
    assert m2.n_tokens == 9 and m2.cow[1] == 1


def test_prefix_cache_survives_free_and_is_reclaimed_lru():
    """Committed pages outlive their sequence (free decrements, cached pages
    stay reclaimable and matchable) and are evicted LRU under pressure."""
    pool = PagedKVPool(n_pages=9, page_size=4)
    a, b = list(range(8)), list(range(100, 108))
    pool.allocate(1, 8)
    pool.commit_prefix(1, a, 8)
    pool.allocate(2, 8)
    pool.commit_prefix(2, b, 8)
    pool.free(1)
    pool.free(2)
    st_ = pool.stats()
    assert st_.cached_pages == 4 and st_.free_pages == 8
    assert pool.match_prefix(a + [1]).n_tokens == 8  # hit after free
    # touch b (LRU refresh), then squeeze: a's pages must be evicted first
    pool.acquire_prefix(3, b + [9])
    pool.free(3)
    pool.allocate(4, 24)               # 6 pages: forces reclaim of a's
    pool.check_invariants()
    assert pool.match_prefix(a + [1]).n_tokens == 0
    assert pool.match_prefix(b + [9]).n_tokens > 0   # recently-used survived


def test_release_yield_counts_exclusive_pages_only():
    pool = PagedKVPool(n_pages=17, page_size=4)
    toks = list(range(12))
    pool.allocate(1, 12)
    pool.commit_prefix(1, toks, 12)
    pool.acquire_prefix(2, toks + [5])
    pool.extend(2, 16)                 # one private page on top of 3 shared
    assert pool.release_yield(2) == 1  # evicting seq 2 reclaims only that
    assert pool.release_yield(1) == 0  # everything seq 1 holds is shared
    pool.free(2)
    assert pool.release_yield(1) == 3


def test_assert_writable_blocks_shared_and_committed_pages():
    pool = PagedKVPool(n_pages=17, page_size=4)
    toks = list(range(10))
    pool.allocate(1, 10)
    pool.commit_prefix(1, toks, 10)    # 2 full nodes + 2-row partial
    pool.acquire_prefix(2, toks + [7, 8])
    pool.extend(2, 16)
    # seq 2 writing into the shared prefix region must be refused
    with pytest.raises(RuntimeError, match="shared"):
        pool.assert_writable(2, 0, 4)
    pool.assert_writable(2, 10, 16)    # its COW fork tail + private page: ok
    # seq 1 may still append to its own partially-committed tail page...
    pool.assert_writable(1, 10, 12)
    # ...but never rewrite the rows it already committed
    with pytest.raises(RuntimeError, match="committed"):
        pool.assert_writable(1, 8, 9)
    # and a full committed page is immutable even once unshared
    pool.free(2)
    with pytest.raises(RuntimeError, match="committed"):
        pool.assert_writable(1, 4, 8)
    pool.check_invariants()


@given(ops=st.lists(st.tuples(st.integers(0, 3), st.integers(1, 40)),
                    min_size=1, max_size=60))
@settings(deadline=None, max_examples=40)
def test_pool_invariants_random_ops(ops):
    """Interleaved allocate/extend/advance/free never double-assigns a page,
    never leaks one, and free-list reuse keeps ``check_invariants`` green."""
    pool = PagedKVPool(n_pages=12, page_size=4)
    live = {}        # seq_id -> reserved tokens
    next_id = 0
    for kind, n_tokens in ops:
        if kind == 0:  # allocate a new sequence
            try:
                pool.allocate(next_id, n_tokens)
                live[next_id] = n_tokens
                next_id += 1
            except PoolOOM:
                pass
        elif kind == 1 and live:  # extend the oldest live sequence
            sid = next(iter(live))
            try:
                pool.extend(sid, live[sid] + n_tokens)
                live[sid] += n_tokens
            except PoolOOM:
                pass
        elif kind == 2 and live:  # advance (utilization accounting only)
            sid = next(iter(live))
            pool.advance(sid, 1)
        elif kind == 3 and live:  # free
            sid = next(iter(live))
            pool.free(sid)
            del live[sid]
        pool.check_invariants()
        # a freed-then-reused page set still never double-owns
        owned = [p for s in live for p in pool.page_table(s)]
        assert len(owned) == len(set(owned))


@given(ops=st.lists(st.tuples(st.integers(0, 5), st.integers(1, 24),
                              st.integers(0, 2)),
                    min_size=1, max_size=60))
@settings(deadline=None, max_examples=40)
def test_pool_invariants_random_ops_with_sharing(ops):
    """allocate/extend/advance/free interleaved with match/acquire (fork),
    commit and release over a 3-token vocabulary (collisions everywhere, so
    sharing actually happens): per-page sequence refcounts always equal the
    number of tables holding the page, trie bookkeeping stays consistent,
    and no page is ever both free and referenced (or cached)."""
    pool = PagedKVPool(n_pages=12, page_size=4)
    vocab = 3
    live: dict[int, list[int]] = {}    # seq_id -> its token list
    next_id = 0
    for kind, n_tokens, tok in ops:
        toks = [(tok + j) % vocab for j in range(n_tokens)]
        if kind == 0:      # fresh exclusive allocation
            try:
                pool.allocate(next_id, n_tokens)
                live[next_id] = toks
                next_id += 1
            except PoolOOM:
                pass
        elif kind == 1 and live:   # extend + append tokens
            sid = next(iter(live))
            try:
                pool.extend(sid, len(live[sid]) + n_tokens)
                live[sid] += toks
            except PoolOOM:
                pass
        elif kind == 2 and live:   # advance (accounting only)
            pool.advance(next(iter(live)), 1)
        elif kind == 3 and live:   # release: refcount decrement
            sid = next(iter(live))
            pool.free(sid)
            del live[sid]
        elif kind == 4:            # acquire via trie match (maybe COW fork)
            sid = next_id
            next_id += 1
            pages, matched, cow = pool.acquire_prefix(sid, toks)
            assert matched < len(toks)     # last token never matched
            assert len(pages) == -(-matched // 4) and len(cow) <= 1
            try:
                pool.extend(sid, len(toks))
                live[sid] = toks
            except PoolOOM:        # acquired but can't cover: clean release
                pool.free(sid)
        elif kind == 5 and live:   # commit the known prefix
            sid = next(iter(live))
            pool.commit_prefix(sid, live[sid], min(n_tokens,
                                                   len(live[sid])))
        pool.check_invariants()
        # independent cross-check of the refcount == holders invariant
        counts: dict[int, int] = {}
        for s in live:
            for p in pool.page_table(s):
                counts[p] = counts.get(p, 0) + 1
        for p, c in counts.items():
            assert pool.refcount(p) == c, (p, c)
        assert all(pool.refcount(p) == counts.get(p, 0)
                   for s in live for p in pool.page_table(s))


# ---------------------------------------------------------------------------
# scheduler: chunk packing, budgets, preemption
# ---------------------------------------------------------------------------


def _req(plen=8, max_new=8, base=0):
    """``base`` offsets the token ids: distinct bases give prompts with no
    shared prefix, so packing tests stay out of the trie-aware admission
    grouping's way (which parks same-prefix followers — tested on its own
    in ``test_plan_defers_shared_prefix_followers``)."""
    return Request(prompt=list(range(base, base + plen)),
                   sampling=SamplingParams(max_new_tokens=max_new))


def _seq(pool, *, plen=8, computed=0, state=RequestState.RUNNING, slot=0,
         order=0):
    """A resident sequence with ``computed`` tokens already in the pool."""
    req = _req(plen=plen)
    req.state = state
    req.num_computed_tokens = computed
    pages = pool.allocate(req.req_id, max(computed, 1))
    seq = Sequence(request=req, slot=slot, page_ids=pages,
                   prefill_target=plen, admit_order=order)
    return seq


def test_plan_packs_chunks_around_decodes():
    pool = PagedKVPool(n_pages=64, page_size=8)
    sched = IterationScheduler(SchedulerConfig(
        max_slots=8, chunk_size=4, max_step_tokens=10))
    d1 = _seq(pool, computed=8, state=RequestState.RUNNING, slot=0, order=0)
    d2 = _seq(pool, computed=8, state=RequestState.RUNNING, slot=1, order=1)
    p1 = _seq(pool, plen=32, computed=4, state=RequestState.PREFILLING,
              slot=2, order=2)
    plan = sched.plan_step([_req(plen=16, base=100)], [d1, d2, p1], pool)
    # 2 mandatory decode tokens + a 4-token chunk for p1 + a 4-token first
    # chunk for the admission fill the 10-token step budget exactly
    assert [(s.req_id, n) for s, n in plan.spans] == \
        [(d1.req_id, 1), (d2.req_id, 1), (p1.req_id, 4)]
    assert [n for _, n in plan.admissions] == [4]
    assert plan.total_tokens == 10
    assert not plan.preemptions


def test_plan_fifo_admission_respects_slots_and_pages():
    pool = PagedKVPool(n_pages=9, page_size=8)  # 8 usable pages
    sched = IterationScheduler(SchedulerConfig(max_slots=3))
    waiting = [_req() for _ in range(5)]        # 8-token prompt = 1 page
    plan = sched.plan_step(waiting, [], pool)
    assert [r for r, _ in plan.admissions] == waiting[:3]  # slot-bound, FIFO
    pool2 = PagedKVPool(n_pages=3, page_size=8)  # 2 usable pages
    plan = sched.plan_step(waiting, [], pool2)
    assert [r for r, _ in plan.admissions] == waiting[:2]  # page-bound


def test_plan_chunks_cap_per_step_prefill():
    pool = PagedKVPool(n_pages=64, page_size=8)
    sched = IterationScheduler(SchedulerConfig(
        max_slots=8, chunk_size=8, max_step_tokens=12))
    waiting = [_req(plen=32, base=100 * i) for i in range(4)]
    plan = sched.plan_step(waiting, [], pool)
    # 8-token chunk for the head + 4 tokens of the next prompt = 12 budget;
    # nobody prefills a whole 32-token prompt in one step
    assert [n for _, n in plan.admissions] == [8, 4]


def test_plan_defers_shared_prefix_followers():
    """Trie-aware admission grouping: of N same-prompt arrivals only the
    leader admits and computes; followers are parked (``prefix_deferred``)
    until the leader's committed pages serve them as cache hits."""
    pool = PagedKVPool(n_pages=64, page_size=8)
    sched = IterationScheduler(SchedulerConfig(max_slots=8, chunk_size=32))
    same = [_req(plen=32) for _ in range(3)]       # identical prompts
    other = _req(plen=32, base=500)                # unrelated prompt
    plan = sched.plan_step(same + [other], [], pool)
    # leader + the unrelated request admit; the two followers are deferred
    # (reordering: `other` admits AHEAD of the queued followers)
    assert [r for r, _ in plan.admissions] == [same[0], other]
    assert plan.prefix_deferred == 2
    # a resident PREFILLING sequence is a leader too
    lead = _seq(pool, plen=32, computed=8, state=RequestState.PREFILLING,
                slot=0, order=0)
    plan = sched.plan_step([_req(plen=32)], [lead], pool)
    assert plan.prefix_deferred == 1 and not plan.admissions
    # grouping off: strict FIFO admits everyone immediately
    sched_off = IterationScheduler(SchedulerConfig(
        max_slots=8, chunk_size=32, prefix_grouping=False))
    plan = sched_off.plan_step(same + [other], [], pool)
    assert len(plan.admissions) == 4 and plan.prefix_deferred == 0


def test_plan_preempts_lowest_priority_for_decode_page():
    pool = PagedKVPool(n_pages=5, page_size=4)   # 4 usable pages
    sched = IterationScheduler(SchedulerConfig(max_slots=4, chunk_size=4))
    # two decoders, each about to cross a page boundary (needs +1 page each),
    # pool full: d_old (order 0) must win, d_new (order 1) is evicted
    d_old = _seq(pool, plen=4, computed=8, state=RequestState.RUNNING,
                 slot=0, order=0)
    d_new = _seq(pool, plen=4, computed=8, state=RequestState.RUNNING,
                 slot=1, order=1)
    assert pool.free_pages == 0
    plan = sched.plan_step([], [d_old, d_new], pool)
    assert plan.preemptions == [d_new]
    assert [(s.req_id, n) for s, n in plan.spans] == [(d_old.req_id, 1)]


def test_plan_multi_victim_preemption_is_lowest_priority_first():
    """Two victims in one plan come back lowest-priority-first, so the
    engine's appendleft requeue leaves the OLDER victim ahead in the queue
    (FIFO re-admission must not invert priority under sustained pressure)."""
    pool = PagedKVPool(n_pages=4, page_size=4)   # 3 usable pages
    sched = IterationScheduler(SchedulerConfig(max_slots=4))
    seqs = [_seq(pool, plen=4, computed=4, state=RequestState.RUNNING,
                 slot=i, order=i) for i in range(3)]
    assert pool.free_pages == 0   # all three need +1 page to decode
    plan = sched.plan_step([], seqs, pool)
    assert plan.preemptions == [seqs[2], seqs[1]]   # youngest evicted first
    assert [(s.req_id, n) for s, n in plan.spans] == [(seqs[0].req_id, 1)]


def test_plan_preempts_for_liveness_when_everyone_stalls():
    pool = PagedKVPool(n_pages=5, page_size=4)   # 4 usable pages
    sched = IterationScheduler(SchedulerConfig(max_slots=4, chunk_size=8))
    p_hi = _seq(pool, plen=32, computed=8, state=RequestState.PREFILLING,
                slot=0, order=0)
    p_lo = _seq(pool, plen=32, computed=8, state=RequestState.PREFILLING,
                slot=1, order=1)
    assert pool.free_pages == 0  # both fully stalled: zero tokens schedulable
    plan = sched.plan_step([], [p_hi, p_lo], pool)
    assert plan.preemptions == [p_lo]
    assert plan.spans and plan.spans[0][0] is p_hi and plan.spans[0][1] > 0


def test_plan_latency_budget_shrinks_chunks():
    class FlatCost:
        def decode_step_ns(self, n, ctx):
            return 10.0 * n

        def prefill_ns(self, n):
            return 1.0 * n

        def decode_step_nj(self, n, ctx):
            return 0.0

    pool = PagedKVPool(n_pages=64, page_size=8)
    d = _seq(pool, computed=8, state=RequestState.RUNNING, slot=0, order=0)
    sc = SchedulerConfig(max_slots=8, chunk_size=32,
                         step_latency_budget_ns=26.0)
    plan = IterationScheduler(sc, FlatCost()).plan_step(
        [_req(plen=32)], [d], pool)
    # decode costs 10; a 32-token chunk would cost 42 > 26 — halved to 16
    assert [n for _, n in plan.admissions] == [16]
    # without a cost model the budget is ignored
    plan = IterationScheduler(sc, None).plan_step([_req(plen=32)], [d], pool)
    assert [n for _, n in plan.admissions] == [32]


def test_plan_latency_budget_never_blocks_lone_progress():
    class HugeCost:
        def decode_step_ns(self, n, ctx):
            return 1e9

        def prefill_ns(self, n):
            return 1e9

        def decode_step_nj(self, n, ctx):
            return 0.0

    pool = PagedKVPool(n_pages=64, page_size=8)
    sc = SchedulerConfig(max_slots=8, step_latency_budget_ns=1.0)
    plan = IterationScheduler(sc, HugeCost()).plan_step([_req()], [], pool)
    assert len(plan.admissions) == 1   # minimum progress beats the SLO


def test_cost_models_price_cached_prefill_near_zero():
    """Satellite: a fully-cached prefill chunk is (near-)zero under both
    cost models — cached tokens are page-table pointer updates, costing
    neither a weight read (HBM) nor bit-serial DAC/ADC cycles (CIM)."""
    from repro.serving import CIMCostModel

    hbm = HBMCostModel.from_model_config(CFG)
    assert hbm.prefill_ns(128) > 0
    assert hbm.prefill_ns(128, cached_tokens=128) == 0.0
    assert hbm.prefill_nj(128, cached_tokens=128) == 0.0
    # partially cached: only the uncached tail pays compute
    assert hbm.prefill_ns(128, cached_tokens=64) < hbm.prefill_ns(128)
    cim = CIMCostModel(CFG, strategy="sparse", seq_len=64)
    assert cim.prefill_ns(128) > 0
    assert cim.prefill_ns(128, cached_tokens=128) == 0.0
    assert cim.prefill_nj(128, cached_tokens=128) == 0.0
    # CIM is per-token linear: caching 64 of 128 == prefilling 64
    assert cim.prefill_ns(128, cached_tokens=64) == cim.prefill_ns(64)


def test_plan_admits_cached_prefill_ahead_of_uncached():
    """Satellite: with the prompt's pages cached, plan_step prices the hit
    request's admission at one token (its whole remaining prefill) and the
    equal-length uncached request only gets the leftover budget — the
    cache hit effectively jumps the packing order."""
    pool = PagedKVPool(n_pages=64, page_size=8)
    shared = list(range(64))
    pool.allocate(99, 64)
    pool.commit_prefix(99, shared, 64)
    pool.free(99)

    hit = Request(prompt=list(shared),
                  sampling=SamplingParams(max_new_tokens=4))
    miss = Request(prompt=list(range(100, 164)),
                   sampling=SamplingParams(max_new_tokens=4))
    sched = IterationScheduler(SchedulerConfig(
        max_slots=4, chunk_size=256, max_step_tokens=16))
    plan = sched.plan_step([hit, miss], [], pool)
    chunks = dict((r.req_id, n) for r, n in plan.admissions)
    # hit: 63 of 64 tokens matched (COW fork recomputes the last) -> its
    # ENTIRE remaining prefill fits in 1 token; miss gets the other 15
    assert chunks[hit.req_id] == 1
    assert chunks[miss.req_id] == 15
    # sharing disabled: the same 16-token budget is swallowed by the hit
    # request's uncached prompt and the miss is shut out entirely
    sched_off = IterationScheduler(SchedulerConfig(
        max_slots=4, chunk_size=256, max_step_tokens=16,
        prefix_sharing=False))
    plan_off = sched_off.plan_step([hit, miss], [], pool)
    assert [(r.req_id, n) for r, n in plan_off.admissions] == \
        [(hit.req_id, 16)]


def test_plan_credits_pages_shared_only_between_victims_once():
    """Regression: a page held by exactly the victims chosen so far frees
    up once the LAST of them goes.  Per-victim exclusive counting credits
    it to neither, so the loop would evict a third (healthy) resident."""
    pool = PagedKVPool(n_pages=7, page_size=4)
    # three high-priority decodes, each about to cross a page boundary
    ds = [_seq(pool, plen=4, computed=4, state=RequestState.RUNNING,
               slot=i, order=i) for i in range(3)]
    # A commits a 2-page prompt; B shares A's first page + COW-forks the rest
    req_a = _req(plen=8)
    req_a.state = RequestState.RUNNING
    req_a.num_computed_tokens = 8
    pages_a = pool.allocate(req_a.req_id, 8)
    pool.commit_prefix(req_a.req_id, req_a.prompt, 8)
    seq_a = Sequence(request=req_a, slot=3, page_ids=pages_a,
                     prefill_target=8, admit_order=3)
    req_b = _req(plen=8)
    req_b.state = RequestState.RUNNING
    req_b.num_computed_tokens = 8
    pages_b, matched, _ = pool.acquire_prefix(req_b.req_id, req_b.prompt)
    assert matched == 7 and pool.refcount(pages_b[0]) == 2
    seq_b = Sequence(request=req_b, slot=4, page_ids=pages_b,
                     prefill_target=8, admit_order=4)
    assert pool.free_pages == 0   # 3 decode + A's 2 + B's fork = 6 usable
    sched = IterationScheduler(SchedulerConfig(max_slots=8))
    plan = sched.plan_step([], ds + [seq_a, seq_b], pool)
    # evicting B (fork) + A (its now-exclusive 2 pages, one of which was
    # shared with B) yields the 3 pages the decodes need — no third victim
    assert plan.preemptions == [seq_b, seq_a]
    assert sorted(s.req_id for s, _ in plan.spans) == \
        sorted(d.req_id for d in ds)


def test_plan_charges_reclaimable_pages_consumed_by_a_hit():
    """Regression: ``free_pages`` counts trie-cached reclaimable pages, but
    an admission whose prefix match refcounts those very pages removes them
    from the reclaimable set — the budget must charge for them, or a
    mandatory decode gets starved at dispatch time."""
    pool = PagedKVPool(n_pages=6, page_size=4)
    committed = list(range(12))
    dec = _seq(pool, plen=8, computed=8, state=RequestState.RUNNING,
               slot=0, order=0)                   # 2 pages, next token needs 1
    pool.allocate(99, 12)
    pool.commit_prefix(99, committed, 12)
    pool.free(99)                                 # 3 cached reclaimable pages
    assert pool.free_pages == 3
    hit = Request(prompt=committed, sampling=SamplingParams(max_new_tokens=4))
    sched = IterationScheduler(SchedulerConfig(max_slots=4, chunk_size=8))
    plan = sched.plan_step([hit], [dec], pool)
    # the hit would pin 2 reclaimable pages + draw 1 fork page = the whole
    # remaining capacity: with the decode's page charged first there is no
    # room, so the admission must wait (it gets in once the decode settles)
    assert [(s.req_id, n) for s, n in plan.spans] == [(dec.req_id, 1)]
    assert plan.admissions == []
    assert not plan.preemptions


def test_hbm_cost_model_amortizes_batch():
    cm = HBMCostModel.from_model_config(CFG)
    one = cm.decode_step_ns(1, 64)
    eight = cm.decode_step_ns(8, 64)
    assert eight < 8 * one    # weight reads amortize over the batch


def test_hbm_prefill_cost_scales_with_tokens():
    """Regression: prefill_ns used to ignore n_tokens (one flat weight pass),
    so a prefill-token budget never actually bound."""
    cm = HBMCostModel.from_model_config(CFG)
    assert cm.prefill_ns(2048) > cm.prefill_ns(256) > cm.prefill_ns(16)
    # compute term: doubling tokens adds exactly one more compute slice
    d1 = cm.prefill_ns(512) - cm.prefill_ns(256)
    d2 = cm.prefill_ns(256) - cm.prefill_ns(128)
    assert d1 == pytest.approx(2 * d2)


# ---------------------------------------------------------------------------
# paged mixed step vs ring cache (logit-level)
# ---------------------------------------------------------------------------


def test_paged_mixed_step_matches_ring_chunked(params):
    """Chunked prefill through paged_mixed_step reproduces the ring-cache
    prefill logits, and span-1 steps reproduce decode_step."""
    B, S, pg, MP = 2, 8, 4, 8
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, CFG.vocab)
    cache = T.init_decode_cache(CFG, B, 32)
    ring_logits, cache = T.prefill_with_cache(params, prompts, cache, CFG)

    pool = T.init_paged_pool(CFG, 1 + B * MP, pg)
    pt = jnp.asarray([[1 + b * MP + j for j in range(MP)] for b in range(B)],
                     jnp.int32)
    start = jnp.zeros((B,), jnp.int32)
    for c0 in range(0, S, 3):   # ragged chunks: 3 + 3 + 2
        n = min(3, S - c0)
        paged_logits, pool = T.paged_mixed_step(
            params, prompts[:, c0:c0 + n], start,
            jnp.full((B,), n, jnp.int32), pt, pool, CFG)
        start = start + n
    np.testing.assert_allclose(np.asarray(ring_logits),
                               np.asarray(paged_logits), rtol=1e-4, atol=1e-4)
    tok = jnp.argmax(ring_logits, -1).astype(jnp.int32)
    for _ in range(3):
        ring_logits, cache = T.decode_step(params, tok, cache, CFG)
        paged_logits, pool = T.paged_mixed_step(
            params, tok[:, None], start, jnp.ones((B,), jnp.int32), pt, pool,
            CFG)
        np.testing.assert_allclose(np.asarray(ring_logits),
                                   np.asarray(paged_logits),
                                   rtol=1e-4, atol=1e-4)
        start = start + 1
        tok = jnp.argmax(ring_logits, -1).astype(jnp.int32)


def test_paged_mixed_step_ragged_spans_write_only_their_span(params):
    """A mixed batch (span 1 decode next to a longer chunk, plus an inert
    span-0 row) only writes each row's real span: padding positions land in
    the sink page, inert rows leave the pool untouched."""
    B, pg, MP = 3, 4, 4
    pool = T.init_paged_pool(CFG, 1 + B * MP, pg)
    pt = jnp.asarray([[1 + b * MP + j for j in range(MP)] for b in range(B)],
                     jnp.int32)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, 4), 0, CFG.vocab)
    before = np.asarray(pool["layers"]["attn"]["k_pages"])[0]  # layer 0
    start = jnp.asarray([5, 0, 0], jnp.int32)
    span = jnp.asarray([1, 4, 0], jnp.int32)
    _, pool = T.paged_mixed_step(params, tokens, start, span, pt, pool, CFG)
    after = np.asarray(pool["layers"]["attn"]["k_pages"])[0]
    # row 2 is inert: its pages (9..12) are untouched
    np.testing.assert_array_equal(before[9:13], after[9:13])
    # row 0 wrote exactly one position: page 2 (pos 5 -> logical page 1),
    # offset 1; the rest of row 0's pages (1, 3, 4) are untouched
    np.testing.assert_array_equal(before[[1, 3, 4]], after[[1, 3, 4]])
    changed = (before[2] != after[2]).any(axis=(-2, -1))
    np.testing.assert_array_equal(changed, [False, True, False, False])


# XLA's CPU backend rounds some narrow matmuls (CFG's 64 -> 32 K/V
# projections among them) differently for different row counts, which
# would blur "bit-identical" below into "equal to an ulp"; at these widths
# it does not, so the packed layout must reproduce the padded one exactly
PACKED_CFG = dataclasses.replace(CFG, n_kv_heads=4)


def _packed_step_case(kv_dtype: str, seed: int = 7):
    """One slot batch of 8 that mixes every kind of row: decodes (slots 0,
    2, 7), two full prefill chunks (1, and 3, a copy-on-write row whose
    first page is slot 0's and whose fork point lies inside the span), a
    tail chunk shorter than the span (5), a one-token prefill (6) and an
    inert slot (4); the pool full of history (random pages and scales)."""
    B, pg, MP, S = 8, 4, 6, 8
    rng = np.random.default_rng(seed)
    pool = T.init_paged_pool(PACKED_CFG, 1 + B * MP, pg, kv_dtype=kv_dtype)
    attn = pool["layers"]["attn"]
    for name in ("k_pages", "v_pages"):
        a = attn[name]
        if kv_dtype == "int8":
            attn[name] = jnp.asarray(rng.integers(-127, 128, a.shape), a.dtype)
        else:
            attn[name] = jnp.asarray(rng.standard_normal(a.shape), a.dtype)
    for name in ("k_scales", "v_scales"):
        if name in attn:
            attn[name] = jnp.asarray(
                rng.uniform(0.005, 0.02, attn[name].shape), jnp.float32)
    pt = 1 + np.arange(B * MP, dtype=np.int32).reshape(B, MP)
    pt[3, 0] = pt[0, 0]                        # slot 3 shares slot 0's page
    use_dev = np.array([1, 0, 1, 0, 0, 0, 0, 1], bool)
    start = np.array([9, 0, 5, 2, 0, 8, 6, 14], np.int32)
    span = np.array([1, 8, 1, 8, 0, 3, 1, 1], np.int32)
    wstart = np.array([0, 0, 0, 4, 0, 0, 0, 0], np.int32)
    sample = np.array([1, 0, 1, 0, 0, 1, 1, 1], bool)
    chunks = np.zeros((B, S), np.int32)        # span rows in slot order
    for i, s in enumerate(np.flatnonzero(~use_dev & (span > 0))):
        chunks[i, :span[s]] = rng.integers(0, CFG.vocab, span[s])
    tok = rng.integers(0, CFG.vocab, B).astype(np.int32)
    args = (tok, use_dev, start, span, pt, wstart, sample)
    return pool, chunks, [jnp.asarray(a) for a in args]


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("attention", ["dense", "kernel"])
def test_packed_step_matches_every_row_in_the_span_group(attention,
                                                         kv_dtype):
    """The mixed step with its span group packed to the bucket of its four
    prefill rows (R = 4) against the same step at R = B: the same greedy
    draws, logits equal to fp32 tolerance, and every page but the sink
    bit-identical — over the dense gather and the Pallas span kernel (in
    interpret mode here), with bf16 and int8 KV pages."""
    from repro.core.quant import KV_DTYPE_BYTES
    from repro.kernels.ops import paged_dispatch
    from repro.serving import engine as E

    cfg = dataclasses.replace(PACKED_CFG, paged_kernel=attention == "kernel")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    for s in (1, 8):   # both groups take the path under test
        want = "kernel" if attention == "kernel" else "disabled"
        assert paged_dispatch(
            s, cfg.n_heads, cfg.hd, 4, cfg.n_kv_heads,
            KV_DTYPE_BYTES[kv_dtype], quantized=kv_dtype == "int8",
            paged_kernel=cfg.paged_kernel) == want
    pool, chunks, (tok, use_dev, start, span, pt, wstart, sample) = \
        _packed_step_case(kv_dtype)
    B = tok.shape[0]
    temp = jnp.zeros((B,), jnp.float32)
    keys = jnp.zeros((B, 2), jnp.uint32)
    logits_fn = jax.jit(E._slot_logits, static_argnames=("cfg",))
    step_fn = jax.jit(E._mixed_step_body, static_argnames=("cfg",))
    out = {}
    for R in (4, B):
        ck = jnp.asarray(chunks[:R])
        copy = jax.tree_util.tree_map(jnp.copy, pool)
        logits, new_pool = logits_fn(params, copy, ck, tok, use_dev, start,
                                     span, pt, wstart, cfg=cfg)
        _, sampled, tok_new, _ = step_fn(
            params, jax.tree_util.tree_map(jnp.copy, pool), ck, tok,
            use_dev, start, span, pt, wstart, sample, temp, keys, cfg=cfg)
        out[R] = (np.asarray(logits), new_pool, np.asarray(tok_new))
    real = np.asarray(span) > 0
    np.testing.assert_allclose(out[4][0][real], out[B][0][real],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(out[4][2], out[B][2])
    assert (out[4][2] != np.asarray(tok))[np.asarray(sample)].any()
    for a, b, before in zip(jax.tree_util.tree_leaves(out[4][1]),
                            jax.tree_util.tree_leaves(out[B][1]),
                            jax.tree_util.tree_leaves(pool)):
        np.testing.assert_array_equal(np.asarray(a)[:, 1:],
                                      np.asarray(b)[:, 1:])
        # the shared page and the inert slot's pages come out as they were
        for p in [pt[0, 0]] + list(np.asarray(pt[4])):
            np.testing.assert_array_equal(np.asarray(a)[:, p],
                                          np.asarray(before)[:, p])


def test_cow_copy_pages_device():
    """Whole-page device copy across every layer's k/v arrays; other pages
    (and sink->sink padding entries) are untouched."""
    pool = T.init_paged_pool(CFG, 6, 4)
    kp = pool["layers"]["attn"]["k_pages"]
    pool["layers"]["attn"]["k_pages"] = kp.at[:, 2].set(1.5)
    vp = pool["layers"]["attn"]["v_pages"]
    pool["layers"]["attn"]["v_pages"] = vp.at[:, 2].set(-2.5)
    before = jax.tree_util.tree_map(np.asarray, pool)
    new = T.cow_copy_pages(pool, jnp.asarray([2, 0]), jnp.asarray([4, 0]))
    for name, want in (("k_pages", 1.5), ("v_pages", -2.5)):
        arr = np.asarray(new["layers"]["attn"][name])
        np.testing.assert_array_equal(arr[:, 4], arr[:, 2])
        assert (arr[:, 4] == want).all()
        np.testing.assert_array_equal(arr[:, [0, 1, 3, 5]],
                                      before["layers"]["attn"][name]
                                      [:, [0, 1, 3, 5]])


def test_write_start_confines_span_writes_to_private_pages(params):
    """The device write-mask derived from the COW fork point: positions of a
    span that fall below ``write_start`` are redirected to the sink, so a
    shared prefix page cannot be written even if the host (erroneously)
    schedules a span across it."""
    B, pg, MP = 1, 4, 4
    pool = T.init_paged_pool(CFG, 1 + MP, pg)
    pt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, 4), 0, CFG.vocab)
    before = np.asarray(pool["layers"]["attn"]["k_pages"])
    # span covers positions 2..5; fork point at 4: positions 2,3 (page 1,
    # nominally shared) must NOT be written, 4,5 (page 2) must be
    _, pool = T.paged_mixed_step(
        params, tokens, jnp.asarray([2], jnp.int32),
        jnp.asarray([4], jnp.int32), pt, pool, CFG,
        write_start=jnp.asarray([4], jnp.int32))
    after = np.asarray(pool["layers"]["attn"]["k_pages"])
    np.testing.assert_array_equal(before[:, 1], after[:, 1])  # shared page
    changed = (before[0, 2] != after[0, 2]).any(axis=(-2, -1))
    np.testing.assert_array_equal(changed, [True, True, False, False])


# ---------------------------------------------------------------------------
# span-aware paged kernel vs oracle
# ---------------------------------------------------------------------------


def _kernel_fixture(B=3, H=4, KV=2, hd=16, pg=4, MP=5, seed=0):
    rng = np.random.default_rng(seed)
    P = 1 + B * MP
    kp = jnp.asarray(rng.standard_normal((P, pg, KV, hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((P, pg, KV, hd)), jnp.float32)
    pt = jnp.asarray(rng.permutation(np.arange(1, P)).reshape(B, MP),
                     jnp.int32)
    return rng, kp, vp, pt


def test_paged_kernel_single_query_matches_ref():
    from repro.kernels.paged import paged_attention
    from repro.kernels.ref import paged_attention_ref

    rng, kp, vp, pt = _kernel_fixture()
    q = jnp.asarray(rng.standard_normal((3, 4, 16)), jnp.float32)
    lengths = jnp.asarray([1, 7, 20], jnp.int32)
    for win in (1_000_000_000, 5):
        out = paged_attention(q, kp, vp, pt, lengths,
                              jnp.asarray(win, jnp.int32))
        ref = paged_attention_ref(q, kp, vp, pt, lengths, win)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_paged_kernel_spans_straddle_page_boundary():
    """Awkward spans: straddling a page boundary, span == page_size, and a
    mixed batch of a span-1 decode next to long chunks."""
    from repro.kernels.paged import paged_attention_span
    from repro.kernels.ref import paged_attention_span_ref

    rng, kp, vp, pt = _kernel_fixture()
    S = 6
    q = jnp.asarray(rng.standard_normal((3, S, 4, 16)), jnp.float32)
    # row 0: span 5 starting at 2 straddles the pos-4 page boundary;
    # row 1: span 4 == page_size, page-aligned start;
    # row 2: span-1 decode deep into its pages — all in ONE mixed batch
    start = jnp.asarray([2, 4, 17], jnp.int32)
    span = jnp.asarray([5, 4, 1], jnp.int32)
    for win in (1_000_000_000, 3):
        out = paged_attention_span(q, kp, vp, pt, start, span,
                                   jnp.asarray(win, jnp.int32))
        ref = paged_attention_span_ref(q, kp, vp, pt, start, span, win)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        # padding rows (i >= span_len) are zeroed, not garbage
        arr = np.asarray(out)
        assert (arr[1, 4:] == 0).all() and (arr[2, 1:] == 0).all()


def test_paged_kernel_full_span_and_zero_start():
    from repro.kernels.paged import paged_attention_span
    from repro.kernels.ref import paged_attention_span_ref

    rng, kp, vp, pt = _kernel_fixture(seed=3)
    S = 8
    q = jnp.asarray(rng.standard_normal((3, S, 4, 16)), jnp.float32)
    start = jnp.asarray([0, 0, 8], jnp.int32)     # fresh prefills + mid-seq
    span = jnp.asarray([8, 3, 8], jnp.int32)      # span 8 = 2 whole pages
    out = paged_attention_span(q, kp, vp, pt, start, span,
                               jnp.asarray(1_000_000_000, jnp.int32))
    ref = paged_attention_span_ref(q, kp, vp, pt, start, span, 1_000_000_000)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# engines: equivalence + lifecycle
# ---------------------------------------------------------------------------


def test_legacy_shim_batched_prefill_matches_seed_path(params):
    """Batched ring prefill produces the same logits as S decode steps."""
    B, S = 2, 8
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, CFG.vocab)
    cache = T.init_decode_cache(CFG, B, 32)
    for t in range(S):
        seq_logits, cache = T.decode_step(params, prompts[:, t], cache, CFG)
    cache2 = T.init_decode_cache(CFG, B, 32)
    bat_logits, cache2 = T.prefill_with_cache(params, prompts, cache2, CFG)
    np.testing.assert_allclose(np.asarray(seq_logits), np.asarray(bat_logits),
                               rtol=1e-5, atol=1e-5)
    assert int(cache2["pos"][0]) == S


@pytest.mark.parametrize("chunk", [16, 64, None])  # None = full prompt
def test_continuous_matches_single_request_greedy(params, chunk):
    """Mixed-step greedy decode is token-identical to the single-request
    engine across chunk sizes (16 / 64 / full-prompt), with mixed prompt
    lengths and staggered joins (max_slots < number of requests forces
    join/evict churn; chunk 16 splits the longest prompt across steps)."""
    lens = [3, 24, 5, 18, 2]
    prompts = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(10 + i), (L,), 0, CFG.vocab))
        for i, L in enumerate(lens)]
    kw = {} if chunk is None else {"chunk_size": chunk}
    eng = ContinuousBatchingEngine(CFG, params, max_slots=2, page_size=4,
                                   max_len=48, **kw)
    reqs = [eng.add_request(p, SamplingParams(max_new_tokens=6))
            for p in prompts]
    finished = eng.run()
    assert len(finished) == len(reqs)
    single = ServeEngine(CFG, params, max_len=48)
    for p, r in zip(prompts, reqs):
        assert r.state is RequestState.FINISHED
        ref = np.asarray(single.generate(
            jnp.asarray(p)[None], GenerationConfig(max_new_tokens=6)))[0]
        np.testing.assert_array_equal(ref, np.asarray(r.output_tokens))
    eng.pool_host.check_invariants()
    assert eng.pool_host.free_pages == eng.pool_host.n_pages - 1


def test_preemption_under_tiny_pool_is_token_identical(params):
    """Regression for the preemption contract: a deliberately tiny pool
    forces evictions mid-flight, and greedy output stays token-identical to
    an uncontended run (pages freed, cursor reset, recompute-on-resume)."""
    lens = [3, 24, 5, 18, 2]
    prompts = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(10 + i), (L,), 0, CFG.vocab))
        for i, L in enumerate(lens)]
    single = ServeEngine(CFG, params, max_len=48)
    eng = ContinuousBatchingEngine(CFG, params, max_slots=4, page_size=4,
                                   max_len=48, n_pages=9, chunk_size=8)
    reqs = [eng.add_request(p, SamplingParams(max_new_tokens=6))
            for p in prompts]
    finished = eng.run()
    assert len(finished) == len(reqs)
    assert eng.stats["preemptions"] > 0, "tiny pool never preempted"
    assert max(r.num_preemptions for r in reqs) > 0
    for p, r in zip(prompts, reqs):
        ref = np.asarray(single.generate(
            jnp.asarray(p)[None], GenerationConfig(max_new_tokens=6)))[0]
        np.testing.assert_array_equal(ref, np.asarray(r.output_tokens))
    eng.pool_host.check_invariants()
    assert eng.pool_host.free_pages == eng.pool_host.n_pages - 1


def _shared_prefix_prompts(n=4, prefix_len=32, tail=3):
    """n prompts sharing a synthetic system prefix, with distinct tails."""
    sys_p = list(np.asarray(jax.random.randint(
        jax.random.PRNGKey(40), (prefix_len,), 0, CFG.vocab)))
    return [np.asarray(sys_p + [(17 * i + j) % CFG.vocab
                                for j in range(tail + i % 2)], np.int32)
            for i in range(n)]


def test_prefix_sharing_greedy_token_identical_and_saves_work(params):
    """Acceptance: greedy outputs are token-identical with prefix sharing
    on vs off, while sharing computes strictly fewer prefill tokens and
    reports its hits.  Requests are staggered so the first sequence's
    committed pages are matchable by the rest (simultaneous identical
    prefills cannot share — nothing is committed yet)."""
    prompts = _shared_prefix_prompts(n=4, prefix_len=32)

    def run(sharing):
        eng = ContinuousBatchingEngine(CFG, params, max_slots=4, page_size=4,
                                       max_len=64, prefix_sharing=sharing)
        reqs = []
        for p in prompts:           # staggered arrivals: one step per submit
            reqs.append(eng.add_request(p, SamplingParams(max_new_tokens=6)))
            for _ in range(12):     # let the head request commit its prefix
                eng.step()
        eng.run()
        eng.pool_host.check_invariants()
        assert eng.pool_host.free_pages == eng.pool_host.n_pages - 1
        return eng, [r.output_tokens for r in reqs]

    eng_on, out_on = run(True)
    eng_off, out_off = run(False)
    assert out_on == out_off
    single = ServeEngine(CFG, params, max_len=64)
    for p, toks in zip(prompts, out_on):
        ref = np.asarray(single.generate(
            jnp.asarray(p)[None], GenerationConfig(max_new_tokens=6)))[0]
        np.testing.assert_array_equal(ref, np.asarray(toks))
    # the sharing run actually shared: hits cover most of 3 x 32-token
    # prefixes, prefill work shrinks accordingly, and stats surface it
    assert eng_on.stats["prefix_hit_tokens"] >= 3 * 24
    assert eng_off.stats["prefix_hit_tokens"] == 0
    assert eng_on.stats["prefill_tokens"] \
        <= eng_off.stats["prefill_tokens"] - 3 * 24
    assert eng_on.pool_host.pages_allocated_total \
        < eng_off.pool_host.pages_allocated_total
    st_ = eng_on.pool_host.stats()
    assert st_.prefix_hit_tokens == eng_on.stats["prefix_hit_tokens"]
    assert 0.0 < st_.prefix_hit_rate <= 1.0


def test_prefix_sharing_token_identical_through_preemption(params):
    """Acceptance: a tiny pool forces preemption of sequences that HOLD
    shared pages; refcount release + trie re-match on resume keeps greedy
    output token-identical to the unshared and uncontended runs."""
    prompts = _shared_prefix_prompts(n=4, prefix_len=16)
    single = ServeEngine(CFG, params, max_len=64)

    def run(sharing, n_pages):
        eng = ContinuousBatchingEngine(
            CFG, params, max_slots=4, page_size=4, max_len=48,
            n_pages=n_pages, chunk_size=8, prefix_sharing=sharing)
        reqs = []
        for p in prompts:
            reqs.append(eng.add_request(p, SamplingParams(max_new_tokens=6)))
            eng.step()
        eng.run()
        eng.pool_host.check_invariants()
        return eng, reqs

    eng, reqs = run(True, n_pages=11)   # deliberately starved
    assert eng.stats["preemptions"] > 0, "tiny pool never preempted"
    assert eng.stats["prefix_hit_tokens"] > 0, "nothing was ever shared"
    for p, r in zip(prompts, reqs):
        ref = np.asarray(single.generate(
            jnp.asarray(p)[None], GenerationConfig(max_new_tokens=6)))[0]
        np.testing.assert_array_equal(ref, np.asarray(r.output_tokens))


def test_add_request_reports_prefix_hint(params):
    eng = ContinuousBatchingEngine(CFG, params, max_slots=2, page_size=4,
                                   max_len=48)
    prompt = list(range(12))
    r1 = eng.add_request(prompt, SamplingParams(max_new_tokens=2))
    assert r1.num_cached_tokens == 0
    eng.run()
    r2 = eng.add_request(prompt, SamplingParams(max_new_tokens=2))
    assert r2.num_cached_tokens == 11   # full hit minus the resampled token
    eng.run()
    assert r2.output_tokens == r1.output_tokens


def test_continuous_generate_compat_api(params):
    B, S, NEW = 4, 8, 8
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, CFG.vocab)
    ref = np.asarray(ServeEngine(CFG, params, max_len=64).generate(
        prompts, GenerationConfig(max_new_tokens=NEW)))
    out = np.asarray(ContinuousBatchingEngine(
        CFG, params, max_slots=4, page_size=4, max_len=32).generate(
            prompts, GenerationConfig(max_new_tokens=NEW)))
    np.testing.assert_array_equal(ref, out)


def test_continuous_kernel_backend_matches(params):
    """The span-aware Pallas kernel path serves chunked prefill + decode
    with outputs identical to the dense gather path."""
    B, S, NEW = 2, 8, 6
    prompts = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, CFG.vocab)
    ref = np.asarray(ContinuousBatchingEngine(
        CFG, params, max_slots=2, page_size=4, max_len=32,
        chunk_size=3).generate(
            prompts, GenerationConfig(max_new_tokens=NEW)))
    out = np.asarray(ContinuousBatchingEngine(
        CFG, params, max_slots=2, page_size=4, max_len=32, chunk_size=3,
        use_paged_kernel=True).generate(
            prompts, GenerationConfig(max_new_tokens=NEW)))
    np.testing.assert_array_equal(ref, out)


def test_streaming_callbacks_and_eos(params):
    """EOS finishes a request early, frees its pages, and the stream saw
    every token in order."""
    prompts = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, CFG.vocab)
    probe = ContinuousBatchingEngine(CFG, params, max_slots=1, page_size=4,
                                     max_len=32)
    first = int(np.asarray(probe.generate(
        prompts, GenerationConfig(max_new_tokens=1)))[0, 0])

    eng = ContinuousBatchingEngine(CFG, params, max_slots=1, page_size=4,
                                   max_len=32)
    seen = []
    req = eng.add_request(
        np.asarray(prompts[0]),
        SamplingParams(max_new_tokens=8, eos_id=first),
        on_token=lambda r, t: seen.append(t))
    eng.run()
    assert req.finish_reason is not None
    assert seen == req.output_tokens
    if req.output_tokens[0] == first:  # greedy emitted EOS immediately
        assert len(req.output_tokens) == 1
        assert req.finish_reason.value == "eos"
    eng.pool_host.check_invariants()
    assert eng.pool_host.free_pages == eng.pool_host.n_pages - 1


def test_incremental_allocation_is_chunk_sized(params):
    """Admission allocates pages for the first CHUNK, not prompt+max_new:
    the cursor's page footprint grows as prefill advances."""
    prompt = np.arange(16) % CFG.vocab
    eng = ContinuousBatchingEngine(CFG, params, max_slots=1, page_size=4,
                                   max_len=64, chunk_size=4)
    eng.add_request(prompt, SamplingParams(max_new_tokens=32))
    eng.step()   # first 4-token chunk: exactly 1 page, not 12
    (seq,) = eng.running.values()
    assert len(seq.page_ids) == 1
    assert seq.request.state is RequestState.PREFILLING
    assert seq.request.num_computed_tokens == 4
    eng.step()
    assert seq.request.num_computed_tokens == 8
    assert len(seq.page_ids) == 2
    eng.run()
    assert eng.pool_host.free_pages == eng.pool_host.n_pages - 1


def test_per_request_seed_determinism(params):
    """Same sampling seed -> same tokens, regardless of arrival order,
    batch composition or chunk size; different seed -> different tokens."""
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (8,), 0,
                                           CFG.vocab))
    other = np.asarray(jax.random.randint(jax.random.PRNGKey(5), (5,), 0,
                                          CFG.vocab))

    def run_with(arrivals, **kw):
        eng = ContinuousBatchingEngine(CFG, params, max_slots=4, page_size=4,
                                       max_len=32, **kw)
        reqs = [eng.add_request(p, sp) for p, sp in arrivals]
        eng.run()
        return reqs

    sp7 = SamplingParams(max_new_tokens=6, temperature=0.9, seed=7)
    a = run_with([(prompt, sp7)])[0]
    b = run_with([(other, SamplingParams(max_new_tokens=6)), (prompt, sp7)],
                 chunk_size=3)[1]
    assert a.output_tokens == b.output_tokens
    c = run_with([(prompt, SamplingParams(max_new_tokens=6, temperature=0.9,
                                          seed=8))])[0]
    assert c.output_tokens != a.output_tokens


def test_first_token_finisher_is_returned(params):
    """A max_new_tokens=1 request finishes on the token sampled by its final
    prefill chunk and must still come back from run()/step()."""
    eng = ContinuousBatchingEngine(CFG, params, max_slots=2, page_size=4,
                                   max_len=32)
    req = eng.add_request(list(range(4)), SamplingParams(max_new_tokens=1))
    finished = eng.run()
    assert finished == [req]
    assert len(req.output_tokens) == 1
    assert eng.pool_host.free_pages == eng.pool_host.n_pages - 1


def test_zero_new_tokens_rejected_and_empty(params):
    eng = ContinuousBatchingEngine(CFG, params, max_slots=1, page_size=4,
                                   max_len=32)
    with pytest.raises(ValueError):
        eng.add_request(list(range(4)), SamplingParams(max_new_tokens=0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0, CFG.vocab)
    out = eng.generate(prompts, GenerationConfig(max_new_tokens=0))
    assert out.shape == (2, 0)


def test_request_rejected_when_pool_too_small(params):
    eng = ContinuousBatchingEngine(CFG, params, max_slots=2, page_size=4,
                                   max_len=32, n_pages=3)  # 2 usable pages
    with pytest.raises(PoolOOM):
        eng.add_request(list(range(8)), SamplingParams(max_new_tokens=8))


def test_request_rejected_when_over_max_len(params):
    eng = ContinuousBatchingEngine(CFG, params, max_slots=1, page_size=4,
                                   max_len=16)
    with pytest.raises(PoolOOM):
        eng.add_request(list(range(12)), SamplingParams(max_new_tokens=8))


def test_legacy_shim_eos_trim_matches_seed_semantics(params):
    """The no-sync shim reproduces the seed's early-break output: columns
    are trimmed at the first step where every row has emitted EOS."""
    eng = ServeEngine(CFG, params, max_len=64)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, CFG.vocab)
    full = np.asarray(eng.generate(prompts, GenerationConfig(max_new_tokens=6)))
    eos = int(full[0, 2])  # greedy repeats: row 0 is done from col <= 2 on
    out = np.asarray(eng.generate(
        prompts, GenerationConfig(max_new_tokens=6, eos_id=eos)))
    done = np.cumsum(full == eos, axis=1) > 0
    cols = done.all(axis=0)
    expect_w = int(np.argmax(cols)) + 1 if cols.any() else 6
    assert out.shape[1] == expect_w
    np.testing.assert_array_equal(out, full[:, :expect_w])
