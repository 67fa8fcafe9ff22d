"""Serving-engine guarantees.

Device-level (subprocess, 4 fake CPU devices, 2x2 mesh — see
test_executor_core.py for the pattern):

(a) continuous-batching engine greedy ids == the one-shot serve path
    (whole-prompt prefill + teacher-forced recompute, no KV reuse) at
    k=1, over a staggered multi-request trace — and the engine's
    compile-cache bucket set is CLOSED at exactly two buckets (step +
    COW copy): a second identical trace pass compiles nothing.
(c) speculative k=2 output ids == k=1 greedy (acceptance is exact for
    greedy self-speculation), with a nonzero draft-acceptance rate.
(d) chunked prefill (cap_t smaller than the prompts) == whole-prompt
    prefill, on a sliding-window arch (gemma3 reduced).
(e) prefix cache: a shared-system-prompt trace produces identical
    output ids with the cache on and off, while the cached run feeds
    exactly ``prefix_hit_rows`` fewer prompt tokens (>= 40% here).
(f) preemption under page pressure never changes output ids; tpot is
    reported as None (and excluded from stats) for single-token
    requests instead of a fake 0.

Host-level (no jax):

(b) paged-KV-pool invariants under random admission/append/free/preempt
    (hypothesis; ``PagedKVPool.check`` asserts the free/referenced
    partition, refcount == table membership and trash-page containment
    after every op), prefix-cache publish/match/adopt/COW semantics,
    scheduler packing laws (budgets, capacity, per-request item
    ordering, per-CHUNK deferral counting) and the round-robin
    starvation regression.
"""

import os
import subprocess
import sys
import textwrap
from collections import Counter

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.serve import (PagedKVPool, SchedulerConfig, Segment,
                         TickScheduler, propose_draft, verify_greedy)

_COMMON = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    jax.config.update("jax_default_matmul_precision", "highest")
    import jax.numpy as jnp
    from repro.launch.mesh import make_mesh

    from repro.configs import get_arch
    from repro.serve import (EngineConfig, Request, ServeEngine,
                             one_shot_generate)

    def llama():
        return get_arch("llama3.2-3b").reduced(n_layers=4, d_model=64,
                                               n_heads=4, head_dim=16,
                                               vocab=256)

    def gemma():
        # n_layers=5 puts one GLOBAL layer (idx 4) among the window-8
        # locals, so both mask paths run
        return get_arch("gemma3-1b").reduced(n_layers=5, d_model=64,
                                             n_heads=4, head_dim=16,
                                             vocab=256)

    def trace(n, seed=7, lo=3, hi=28, max_new=6, spread=0.4):
        rng = np.random.default_rng(seed)
        out = []
        for i in range(n):
            ln = int(rng.integers(lo, hi))
            out.append(Request(
                req_id=i, prompt=rng.integers(0, 256, ln).astype(np.int32),
                max_new_tokens=max_new, arrival=float(i) * spread))
        return out

    def run_engine(cfg, mesh, econf, reqs, params=None, cache=None,
                   seed=3):
        eng = ServeEngine(cfg, mesh, econf, params=params,
                          param_dtype=jnp.float32, cache=cache, seed=seed)
        res = eng.run(reqs)
        return eng, {r: res[r].output_ids for r in res}
""")


def _run(case: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c",
                        _COMMON + textwrap.dedent(case)],
                       capture_output=True, text=True, env=env, timeout=900)
    if r.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{r.stdout}\n"
            f"STDERR:\n{r.stderr[-4000:]}")
    assert "OK" in r.stdout


# ---------------------------------------------------------------------------
# (a) engine == one-shot path at k=1; bucket set closed on a replay
# ---------------------------------------------------------------------------

def test_engine_matches_one_shot_and_bucket_closure():
    _run("""
        cfg = llama()
        mesh = make_mesh((2, 2), ("data", "model"))
        econf = EngineConfig(n_items=4, cap_t=16, n_pages=24, page_sz=8,
                             pages_per_seq=6, k=1)
        reqs = trace(20, max_new=5)
        eng, got = run_engine(cfg, mesh, econf, reqs)
        assert len(got) == 20, got.keys()

        # exactly two buckets (engine step + COW copy, the copy program
        # built eagerly); replaying the identical trace compiles nothing
        assert eng.cache.stats.misses == 2, eng.cache.stats.as_dict()
        eng2, got2 = run_engine(cfg, mesh, econf, trace(20, max_new=5),
                                params=eng.params, cache=eng.cache)
        assert eng.cache.stats.misses == 2, eng.cache.stats.as_dict()
        assert got2 == got

        # the one-shot serve path (no continuous batching, no KV reuse)
        # produces identical ids for every request
        ref = one_shot_generate(cfg, mesh, eng.params,
                                [r.prompt for r in reqs], 5)
        for r in reqs:
            assert got[r.req_id] == ref[r.req_id], (
                r.req_id, len(r.prompt), got[r.req_id], ref[r.req_id])
        print("OK one-shot parity", sum(map(len, got.values())))
    """)


# ---------------------------------------------------------------------------
# (c) speculative k=2 == k=1 greedy (exact acceptance), drafts accepted
# ---------------------------------------------------------------------------

def test_speculative_k2_matches_k1():
    _run("""
        cfg = llama()
        mesh = make_mesh((2, 2), ("data", "model"))
        reqs = lambda: trace(8, seed=11, max_new=6)
        e1, g1 = run_engine(
            cfg, mesh, EngineConfig(n_items=4, cap_t=16, n_pages=24,
                                    page_sz=8, pages_per_seq=6, k=1), reqs())
        e2, g2 = run_engine(
            cfg, mesh, EngineConfig(n_items=4, cap_t=16, n_pages=24,
                                    page_sz=8, pages_per_seq=6, k=2), reqs(),
            params=e1.params)
        assert g2 == g1, (g1, g2)
        sp = e2.spec_stats
        assert sp.drafted > 0 and sp.decode_ticks > 0
        # zipf-ish tokens repeat, so the n-gram self-draft must land some
        assert sp.accepted > 0, sp.as_dict()
        assert e1.spec_stats.drafted == 0
        print("OK speculative", sp.as_dict())
    """)


# ---------------------------------------------------------------------------
# (d) chunked prefill == whole-prompt prefill (sliding-window arch)
# ---------------------------------------------------------------------------

def test_chunked_prefill_matches_whole_prompt():
    _run("""
        cfg = gemma()
        mesh = make_mesh((2, 2), ("data", "model"))
        reqs = lambda: trace(6, seed=5, lo=10, hi=30, max_new=4)
        # cap_t=8 slices every prompt into multiple pipelined chunks;
        # cap_t=32 prefills each prompt whole
        e_chunk, g_chunk = run_engine(
            cfg, mesh, EngineConfig(n_items=6, cap_t=8, n_pages=32,
                                    page_sz=8, pages_per_seq=8, k=1), reqs())
        e_whole, g_whole = run_engine(
            cfg, mesh, EngineConfig(n_items=4, cap_t=32, n_pages=32,
                                    page_sz=8, pages_per_seq=8, k=1), reqs(),
            params=e_chunk.params)
        assert g_chunk == g_whole, (g_chunk, g_whole)
        ref = one_shot_generate(cfg, mesh, e_chunk.params,
                                [r.prompt for r in reqs()], 4)
        assert g_chunk == {i: ref[i] for i in range(len(ref))}
        print("OK chunked prefill", g_chunk[0])
    """)


# ---------------------------------------------------------------------------
# (e) prefix cache: bitwise-equal outputs, exact prefill-token accounting
# ---------------------------------------------------------------------------

def test_prefix_cache_parity_and_savings():
    _run("""
        cfg = llama()
        mesh = make_mesh((2, 2), ("data", "model"))
        # shared 16-token system prompt (= 2 full pages) + unique tails,
        # staggered so request 0's pages are published before the rest admit
        rng = np.random.default_rng(17)
        sysp = rng.integers(0, 256, 16).astype(np.int32)
        reqs = []
        for i in range(8):
            tail = rng.integers(0, 256,
                                int(rng.integers(5, 11))).astype(np.int32)
            reqs.append(Request(req_id=i,
                                prompt=np.concatenate([sysp, tail]),
                                max_new_tokens=4, arrival=float(i) * 2.0))
        geom = dict(n_items=4, cap_t=16, n_pages=24, page_sz=8,
                    pages_per_seq=5, k=1)
        e_on, g_on = run_engine(cfg, mesh, EngineConfig(**geom), list(reqs))
        e_off, g_off = run_engine(
            cfg, mesh, EngineConfig(prefix_cache=False, **geom),
            list(reqs), params=e_on.params)
        # sharing may never change what comes out
        assert g_on == g_off, (g_on, g_off)
        hits = e_on.pool.stats.prefix_hit_rows
        assert hits > 0, e_on.pool.stats.as_dict()
        assert e_off.pool.stats.prefix_hit_rows == 0
        fed_on = e_on.stats()["prefill_tokens_fed"]
        fed_off = e_off.stats()["prefill_tokens_fed"]
        # every adopted row is a prompt token NOT fed — exact accounting
        assert fed_on + hits == fed_off, (fed_on, hits, fed_off)
        assert (fed_off - fed_on) / fed_off >= 0.40, (fed_on, fed_off)
        print("OK prefix cache", fed_on, "of", fed_off, "fed")
    """)


# ---------------------------------------------------------------------------
# (b) paged pool invariants under random admission/append/free/preempt
# ---------------------------------------------------------------------------

@settings(max_examples=150, deadline=None)
@given(st.integers(2, 12), st.integers(1, 6), st.booleans(),
       st.lists(st.tuples(st.integers(0, 3), st.integers(0, 1000)),
                max_size=80))
def test_paged_pool_invariants(n_pages, page_sz, cache, ops):
    pool = PagedKVPool(n_pages, page_sz, prefix_cache=cache)
    live = {}                    # rid -> pages appended (table length)
    next_rid = 0
    for action, pick in ops:
        if action == 0:
            pool.alloc_table(next_rid)
            live[next_rid] = 0
            next_rid += 1
        elif action == 1 and live:
            rid = sorted(live)[pick % len(live)]
            page = pool.append_page(rid)
            if page is None:
                assert pool.n_free == 0      # only an exhausted pool fails
            else:
                assert 0 <= page < n_pages   # trash page never handed out
                live[rid] += 1
        elif action == 2 and live:
            rid = sorted(live)[pick % len(live)]
            # publish the full pages, then finish: freed pages stay cached
            toks = [(rid * 131 + j) % 7
                    for j in range(live.pop(rid) * page_sz)]
            pool.publish_ready(rid, toks, len(toks))
            pool.free_table(rid)
        elif action == 3 and live:
            rid = sorted(live)[pick % len(live)]
            pool.preempt(rid)
            del live[rid]
        pool.check()
        assert pool.n_seqs == len(live)
        assert pool.in_use + pool.n_free == n_pages
        assert pool.table_of(12345) is None
    assert pool.stats.peak_in_use <= n_pages


@settings(max_examples=100, deadline=None)
@given(st.lists(st.integers(0, 3), min_size=1, max_size=24),
       st.lists(st.integers(0, 3), min_size=1, max_size=24),
       st.integers(1, 4))
def test_prefix_match_never_exceeds_true_common_prefix(a, b, ps):
    pool = PagedKVPool(16, ps)
    pool.alloc_table(1)
    for _ in range(-(-len(a) // ps)):
        pool.append_page(1)
    pool.publish_ready(1, a, len(a))
    pool.free_table(1)
    pages, rows = pool.match_prefix(b, len(b))
    common = 0
    while common < min(len(a), len(b)) and a[common] == b[common]:
        common += 1
    # matched rows are a TRUE shared prefix (hash + token comparison),
    # never an overclaim — this is what makes adoption bitwise-safe
    assert rows <= common, (a, b, rows, common)
    assert len(pages) <= -(-rows // ps) + (1 if rows == 0 else 0)
    pool.check()


def test_prefix_cache_publish_match_adopt_roundtrip():
    pool = PagedKVPool(8, 4)
    pool.alloc_table(1)
    for _ in range(3):
        pool.append_page(1)
    toks = list(range(10))
    pool.publish_ready(1, toks, committed=10)    # 2 full pages published
    assert pool.stats.published == 2
    t1 = list(pool.table_of(1))
    pool.free_table(1)
    pool.check()
    # same prompt: the SAME page ids come back (the device rows are
    # reused verbatim, the definition of a bitwise prefix hit)
    pages, rows = pool.match_prefix(toks, max_rows=9)
    assert rows == 8 and pages == t1[:2]
    pool.alloc_table(2)
    pool.adopt_prefix(2, pages, rows)
    assert pool.refcount(t1[0]) == 1
    assert pool.stats.prefix_hit_rows == 8
    pool.check()
    # a prompt diverging INSIDE page 2 partially matches it (shared rows
    # only up to the divergence point)
    div = toks[:6] + [99, 98]
    p2, r2 = pool.match_prefix(div, max_rows=8)
    assert r2 == 6 and p2 == t1[:2]


def test_cow_never_mutates_shared_page():
    pool = PagedKVPool(6, 4)
    pool.alloc_table(1)
    pool.append_page(1)
    pool.append_page(1)
    toks = list(range(8))
    pool.publish_ready(1, toks, 8)
    pool.alloc_table(2)
    pages, rows = pool.match_prefix(toks[:6] + [50, 51], 7)
    assert rows == 6 and len(pages) == 2         # full page + partial tail
    pool.adopt_prefix(2, pages, rows)
    shared = pages[1]
    assert pool.refcount(shared) == 2
    status, pair = pool.ensure_writable(2, 1)
    assert status == "cow" and pair[0] == shared
    # the shared page is untouched: still in table 1, still published
    assert pool.table_of(1)[1] == shared
    assert pool.is_published(shared)
    assert pool.refcount(shared) == 1
    assert pool.table_of(2)[1] == pair[1] != shared
    assert pool.stats.cow_copies == 1
    pool.check()


def test_ensure_writable_unpublishes_sole_owner_in_place():
    pool = PagedKVPool(4, 4)
    pool.alloc_table(1)
    pool.append_page(1)
    pool.publish_ready(1, list(range(4)), 4)
    p = pool.table_of(1)[0]
    assert pool.is_published(p)
    status, pair = pool.ensure_writable(1, 0)
    assert status == "ok" and pair is None       # in place, hash dropped
    assert not pool.is_published(p)
    assert pool.stats.cow_copies == 0
    pool.check()


def test_cached_free_pages_are_evicted_lru():
    pool = PagedKVPool(2, 2)
    pool.alloc_table(1)
    pool.append_page(1)
    pool.append_page(1)
    pool.publish_ready(1, [1, 2, 3, 4], 4)
    pool.free_table(1)
    assert pool.n_free == 2                      # free-but-cached
    # a fresh allocation reuses the LRU cached page and drops its hash
    pool.alloc_table(2)
    p = pool.append_page(2)
    assert not pool.is_published(p)
    assert pool.stats.cache_evictions == 1
    # the evicted page headed the chain, so the whole prefix stops matching
    pages, rows = pool.match_prefix([1, 2, 3, 4], 4)
    assert rows == 0 and pages == []
    pool.check()


def test_paged_pool_errors_and_exhaustion():
    pool = PagedKVPool(2, 4)
    pool.alloc_table(1)
    with pytest.raises(ValueError):
        pool.alloc_table(1)                      # double admission
    assert pool.append_page(1) is not None
    assert pool.append_page(1) is not None
    assert pool.append_page(1) is None           # exhausted, not fatal
    assert pool.stats.alloc_failures == 1
    with pytest.raises(ValueError):
        pool.free_table(2)                       # unknown request
    assert len(pool.preempt(1)) == 2
    assert pool.stats.preemptions == 1
    assert pool.n_free == 2 and pool.in_use == 0
    pool.check()


# ---------------------------------------------------------------------------
# scheduler packing laws
# ---------------------------------------------------------------------------

def _dec(rid, k=1, base=10):
    return Segment(req_id=rid, kind="decode", tokens=tuple(range(k)),
                   base=base)


def _pre(rid, lens):
    segs, off = [], 0
    for ln in lens:
        segs.append(Segment(req_id=rid, kind="prefill",
                            tokens=tuple(range(ln)), base=off))
        off += ln
    return segs


def test_scheduler_capacity_and_ordering():
    sched = TickScheduler(SchedulerConfig(n_items=3, cap_t=8, k=1))
    plan = sched.plan([_dec(0), _dec(1)], [_pre(2, [8, 8, 8, 8])])
    # never over cap_t per item
    for item in plan.items:
        assert sum(len(s.tokens) for s in item) <= 8
    # same-request segments in strictly increasing item indices (the
    # pipeline ordering that makes chunk j+1 see chunk j's cache writes)
    seen = {}
    for i, item in enumerate(plan.items):
        for s in item:
            assert seen.get(s.req_id, -1) < i
            seen[s.req_id] = i
    # chunks 3 and 4 of request 2 cannot fit this step: BOTH are counted
    # deferred (the field is a chunk count — counting one per request
    # undercounted deferral on skewed traces), never reordered/truncated
    placed_pre = [s for it in plan.items for s in it if s.req_id == 2]
    assert len(placed_pre) == 2
    assert [s.base for s in placed_pre] == sorted(s.base for s in placed_pre)
    assert plan.deferred_prefill == 2
    assert plan.decode_tokens == 2


def test_scheduler_budgets_and_serial_mode():
    # decode budget caps streams per step (round-robin defers the rest)
    sched = TickScheduler(SchedulerConfig(n_items=2, cap_t=4, k=2,
                                          decode_token_budget=4))
    plan = sched.plan([_dec(i, k=2) for i in range(4)], [])
    assert plan.decode_tokens == 4 and plan.deferred_decode == 2
    # round-robin start rotates so deferred streams go first next step
    plan2 = sched.plan([_dec(i, k=2) for i in range(4)], [])
    first_ids = {s.req_id for it in plan.items for s in it}
    second_ids = {s.req_id for it in plan2.items for s in it}
    assert first_ids != second_ids
    # serial (stop-the-world) mode: no decode while prefill is pending
    sched = TickScheduler(SchedulerConfig(n_items=2, cap_t=8, k=1,
                                          prefill_mode="serial"))
    plan = sched.plan([_dec(0)], [_pre(1, [8])])
    kinds = {s.kind for it in plan.items for s in it}
    assert kinds == {"prefill"} and plan.deferred_decode == 1
    # ...and decodes run once nothing is prefilling
    plan = sched.plan([_dec(0)], [])
    assert {s.kind for it in plan.items for s in it} == {"decode"}


def test_scheduler_round_robin_starvation_regression():
    # the rotation is keyed on stable req_id order, not an index into the
    # CURRENT candidate list — with a fixed population every stream must
    # be served the same number of times over a full cycle
    sched = TickScheduler(SchedulerConfig(n_items=2, cap_t=4, k=2,
                                          decode_token_budget=4))
    ids = [3, 7, 11, 20]
    served = Counter()
    for _ in range(8):                       # 2 cycles of 4 streams
        plan = sched.plan([_dec(i, k=2) for i in ids], [])
        for it in plan.items:
            for s in it:
                served[s.req_id] += 1
    assert served == {i: 4 for i in ids}, served
    # population churn: a stream completing mid-rotation must not leave
    # any survivor persistently ordered last (the old index-mod-len bug)
    sched = TickScheduler(SchedulerConfig(n_items=2, cap_t=4, k=2,
                                          decode_token_budget=4))
    pop = [0, 1, 2, 3]
    served = Counter()
    for step in range(9):
        plan = sched.plan([_dec(i, k=2) for i in pop], [])
        for it in plan.items:
            for s in it:
                served[s.req_id] += 1
        if step == 1:
            pop.remove(1)
    survivors = [served[i] for i in pop]
    assert min(survivors) > 0
    assert max(survivors) - min(survivors) <= 1, (served, pop)


# ---------------------------------------------------------------------------
# speculative draft/verify rules (host-side)
# ---------------------------------------------------------------------------

def test_verify_greedy_rules():
    # k=1: emit exactly the model's one id
    assert verify_greedy([5], [9]) == [9]
    # full acceptance: drafts equal the model's ids shifted by one
    assert verify_greedy([5, 9, 4], [9, 4, 7]) == [9, 4, 7]
    # first disagreement stops acceptance; its correction is emitted
    assert verify_greedy([5, 9, 4], [9, 8, 7]) == [9, 8]
    assert verify_greedy([5, 1, 4], [9, 8, 7]) == [9]
    with pytest.raises(ValueError):
        verify_greedy([5, 9], [1])


def test_propose_draft_ngram_lookup():
    # the continuation of the last occurrence of the suffix is proposed
    hist = [1, 2, 3, 7, 8, 1, 2, 3]
    assert propose_draft(hist, 2, ngram=3) == [7, 8]
    # no match: repeat the last token
    assert propose_draft([4, 5, 6], 3, ngram=3) == [6, 6, 6]
    assert propose_draft([], 2) == [0, 0]
    assert propose_draft(hist, 0) == []
    # deterministic and bounded
    assert len(propose_draft(hist, 5)) == 5


# ---------------------------------------------------------------------------
# (f) preemption under page pressure; outputs NEVER change
# ---------------------------------------------------------------------------

def test_preemption_preserves_outputs():
    _run("""
        cfg = llama()
        mesh = make_mesh((2, 2), ("data", "model"))
        reqs = lambda: trace(5, seed=9, lo=12, hi=21, max_new=8, spread=1.0)
        # 6 pages for 5 staggered requests wanting up to 4 each: arrivals
        # 2+ hit an occupied pool (pages are charged on write, so the
        # admission gate only bites once earlier streams hold real pages)
        # and queue-head starvation must evict decode streams...
        tight = EngineConfig(n_items=4, cap_t=24, n_pages=6, page_sz=8,
                             pages_per_seq=4, k=1, preempt_waiting_steps=2)
        e_t, g_t = run_engine(cfg, mesh, tight, reqs())
        assert e_t.pool.stats.preemptions > 0, e_t.pool.stats.as_dict()
        assert any(r.preempted for r in e_t.results.values())
        # ...and greedy determinism means the emitted ids are identical to
        # an uncontended run (only latency moves)
        roomy = EngineConfig(n_items=4, cap_t=24, n_pages=20, page_sz=8,
                             pages_per_seq=4, k=1)
        e_r, g_r = run_engine(cfg, mesh, roomy, reqs(), params=e_t.params)
        assert e_r.pool.stats.preemptions == 0
        assert g_t == g_r, (g_t, g_r)
        print("OK preemption", e_t.pool.stats.as_dict())
    """)


def test_rejections_and_tpot_reporting():
    _run("""
        cfg = llama()
        mesh = make_mesh((2, 2), ("data", "model"))
        econf = EngineConfig(n_items=4, cap_t=16, n_pages=16, page_sz=8,
                             pages_per_seq=4, k=1)
        eng = ServeEngine(cfg, mesh, econf, param_dtype=jnp.float32, seed=3)
        reqs = trace(3, seed=2, lo=4, hi=10, max_new=4)
        # two single-token requests: tpot must come back None, not 0.0
        reqs += [Request(req_id=10 + i,
                         prompt=(np.arange(5 + i) % 256).astype(np.int32),
                         max_new_tokens=1, arrival=0.0) for i in range(2)]
        # prompt + max_new exceeds pages_per_seq * page_sz: rejected, not
        # fatal, and the rest of the trace still completes
        reqs.append(Request(req_id=99,
                            prompt=np.zeros(40, np.int32),
                            max_new_tokens=4, arrival=0.0))
        res = eng.run(reqs)
        assert sorted(res) == [0, 1, 2, 10, 11]
        assert list(eng.rejected) == [99], eng.rejected
        assert "never silently truncated" in eng.rejected[99]
        st = eng.stats()
        assert st["rejected"] == 1
        # single-token requests report tpot_s=None and are EXCLUDED from
        # the percentiles (reporting 0.0 biased them optimistic)
        ones = [r for r in res.values() if len(r.output_ids) == 1]
        multi = [r for r in res.values() if len(r.output_ids) > 1]
        assert len(ones) == 2 and len(multi) == 3
        assert all(r.tpot_s is None for r in ones)
        assert all(r.tpot_s is not None and r.tpot_s >= 0 for r in multi)
        assert st["tpot_measured"] == len(multi)
        print("OK rejection+tpot", eng.rejected[99][:40])
    """)
