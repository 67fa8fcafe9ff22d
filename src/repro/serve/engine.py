"""Continuous-batching serving engine on the EPP pipeline.

Request lifecycle::

    submit() ──> waiting ──admit (page table + prefix match)──> prefill ──> decode ──> done
                   │                                              │            │
                   └── queue (pages short / budget) ──────────────┴── step() packs both
                       into ONE fixed-shape engine program per step

Every :meth:`ServeEngine.step` builds one packed batch for the compiled
engine program (``runtime.serve_step.engine_step_fn``): decode segments
(k tokens per running stream — speculative drafts verified on the host)
co-scheduled with chunked-prefill segments (prompts sliced by the
trainer's ``core.chunking.prompt_slices`` capacity logic). KV rows live
in a PAGED pool (``kv_manager.PagedKVPool`` host-side, the
sequence-sharded device buffer in ``runtime.serve_step``): admission
reserves nothing, pages are allocated on write, and chunked prefill
skips whole pages whose chain hash is already resident (prefix cache) —
shared pages are refcounted and copy-on-write protected, with the page
copies batched through a second tiny compiled program. Because
per-request lengths and page tables are data rather than shape, the
compile cache sees exactly TWO bucket keys per engine configuration
(``compile_cache.engine_bucket_key`` + ``engine_copy_bucket_key``, both
built deterministically) — the second pass over any trace compiles
nothing, and a persistent :class:`CacheStore` warm-starts even the first.

Prefix sharing is exact: a cached page's rows are a deterministic
function of the full token prefix (the chain hash pins it) computed by
the SAME compiled program, and masked attention scores underflow to
exact zeros, so adopted pages are bitwise identical to recomputed ones
and greedy outputs cannot change (runtime/README.md §Paged KV pool).

:func:`one_shot_generate` is the parity oracle: the pre-engine one-shot
serve path (whole-prompt prefill through ``pipeline_loss_fn``'s prefill
mode, teacher-forced full recompute per emitted token — no KV reuse). The
engine's greedy output ids must match it exactly at every ``k``
(tests/test_serve_engine.py).
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .kv_manager import PagedKVPool
from .scheduler import SchedulerConfig, Segment, StepPlan, TickScheduler
from .speculative import SpecStats, propose_draft, verify_greedy

__all__ = ["EngineConfig", "Request", "RequestResult", "ServeEngine",
           "one_shot_generate"]


@dataclass
class EngineConfig:
    """Host-visible engine knobs. (n_items, cap_t, n_pages, page_sz,
    pages_per_seq, k, copy_cap) are the compiled geometry — one step + one
    copy bucket per distinct tuple; the budgets, the prefill mode and the
    prefix cache are pure host policy (no recompile)."""
    n_items: int = 4             # packed chunk items per engine step
    cap_t: int = 64              # tokens per item
    n_pages: int = 16            # KV pages pool-wide (scales with d_s)
    page_sz: int = 16            # cache rows per page
    pages_per_seq: Optional[int] = None   # table entries (None = n_pages);
    # pages_per_seq * page_sz is the max context of one request
    k: int = 1                   # decode tokens per stream per step
    copy_cap: int = 4            # COW page copies per copy-program call
    prefix_cache: bool = True    # content-addressed page sharing
    prefill_chunk: Optional[int] = None   # max prefill chunk (default cap_t)
    decode_token_budget: Optional[int] = None
    prefill_token_budget: Optional[int] = None
    prefill_mode: str = "interleaved"     # | "serial" (stop-the-world)
    draft_ngram: int = 3
    sim_dt: float = 1.0          # simulated seconds per engine step
    # preempt a decode stream when the admission queue's head has waited
    # this many steps without the pages to admit it (None = never): the
    # victim's pages are freed (but stay prefix-cached) and it requeues for
    # a resume-prefill of its history — outputs are unchanged (greedy is
    # deterministic), only latency moves
    preempt_waiting_steps: Optional[int] = None


@dataclass
class Request:
    req_id: int
    prompt: np.ndarray           # int32 [L]
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    arrival: float = 0.0         # simulated arrival time


@dataclass
class RequestResult:
    req_id: int
    prompt_len: int
    output_ids: List[int]
    submitted_step: int
    first_token_step: int        # TTFT in engine steps
    finished_step: int
    ttft_s: float                # wall-clock submit -> first token
    # wall-clock mean per output token after the 1st; None when fewer than
    # 2 tokens were emitted (a single-token request HAS no inter-token
    # latency — reporting 0.0 and filtering ">0" silently biased the
    # percentiles optimistic on short-output traces)
    tpot_s: Optional[float]
    preempted: int = 0           # times this request lost its pages

    @property
    def ttft_steps(self) -> int:
        return self.first_token_step - self.submitted_step


@dataclass
class _ReqState:
    req: Request
    phase: str = "waiting"       # waiting | prefill | decode | done
    committed: int = 0           # valid cache rows (tokens fed & accepted)
    chunks: List[Tuple[int, int]] = field(default_factory=list)
    next_chunk: int = 0
    # tokens being prefilled: the prompt on first admission; on a resume
    # after preemption, history[:-1] (everything but the un-fed last token)
    prefill_target: List[int] = field(default_factory=list)
    waiting_since: int = 0
    next_token: int = -1         # last emitted, not yet fed token
    output: List[int] = field(default_factory=list)
    history: List[int] = field(default_factory=list)  # prompt + output
    submitted_step: int = 0
    submit_wall: float = 0.0
    first_token_step: int = -1
    first_wall: float = 0.0
    finished_step: int = -1
    done_wall: float = 0.0
    preempted: int = 0


class ServeEngine:
    """Continuous-batching engine over one compiled EPP stage program."""

    def __init__(self, cfg_arch, mesh, config: EngineConfig, *,
                 params: Optional[Dict] = None, param_dtype=None,
                 compute_dtype=None, cache=None, store=None,
                 seed: int = 0, log: Optional[Callable] = None,
                 timeline=None):
        import jax
        import jax.numpy as jnp

        from repro.core import ClusterSpec, CostModel
        from repro.runtime.compile_cache import CompileCache
        from repro.runtime.serve_step import (EngineStepBuilder,
                                              make_engine_geometry)

        self.cfg_arch = cfg_arch
        self.mesh = mesh
        self.config = config
        self.log = log
        # optional telemetry.StepTimeline: run() records one "engine"
        # event per drained trace (TTFT/TPOT percentiles + occupancy)
        self.timeline = timeline
        param_dtype = param_dtype or jnp.float32
        compute_dtype = compute_dtype or param_dtype
        self.geom = make_engine_geometry(
            cfg_arch, mesh, n_items=config.n_items, cap_t=config.cap_t,
            n_pages=config.n_pages, page_sz=config.page_sz,
            pages_per_seq=config.pages_per_seq, k=config.k,
            copy_cap=config.copy_cap, compute_dtype=compute_dtype)
        self.builder = EngineStepBuilder(cfg_arch, mesh, self.geom,
                                         param_dtype=param_dtype)
        self.params = params if params is not None else \
            self.builder.init_params(jax.random.PRNGKey(seed))
        self._params_shape = jax.eval_shape(lambda: self.params)
        self.cache = cache if cache is not None else \
            CompileCache(name="serve-engine", log=log, store=store)
        self.pool_state = self.builder.init_pool()
        self.pool = PagedKVPool(config.n_pages, config.page_sz,
                                prefix_cache=config.prefix_cache)
        self.scheduler = TickScheduler(SchedulerConfig(
            n_items=config.n_items, cap_t=config.cap_t, k=config.k,
            decode_token_budget=config.decode_token_budget,
            prefill_token_budget=config.prefill_token_budget,
            prefill_mode=config.prefill_mode))
        # prompt slicing reuses the trainer's workload-balanced capacity
        # logic (Alg. 1 line 1) — chunked prefill IS token-level PP
        pod, data, model = _axes(mesh)
        self._cm = CostModel(cfg_arch.spec,
                             ClusterSpec(d_p=mesh.shape[data],
                                         d_s=mesh.shape[model]))
        self.spec_stats = SpecStats()
        self._waiting: "deque[_ReqState]" = deque()
        self._running: List[_ReqState] = []      # prefill + decode phases
        self._states: Dict[int, _ReqState] = {}
        self.results: Dict[int, RequestResult] = {}
        self.rejected: Dict[int, str] = {}
        self.step_count = 0
        self.sim_time = 0.0
        self._emitted_total = 0
        self._prefill_fed = 0    # prompt tokens actually fed (prefix-cache
        self._run_wall = 0.0     # hits reduce this — the benchmark's gate)
        # build the COW copy program EAGERLY: the serve bucket set must be
        # deterministically closed (2 buckets) whether or not the trace
        # ever triggers a copy — pass 2 compiles nothing either way
        self._copy_fn = self.cache.get(self.copy_bucket_key,
                                       self.builder.build_copy)

    # ------------------------------------------------------------------
    @property
    def bucket_key(self):
        from repro.runtime.compile_cache import engine_bucket_key
        return engine_bucket_key(self.geom)

    @property
    def copy_bucket_key(self):
        from repro.runtime.compile_cache import engine_copy_bucket_key
        return engine_copy_bucket_key(self.geom)

    def _build_step(self):
        return self.builder.build(self._params_shape)

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        """Queue a request. Admission is validated against the page-table
        geometry up front — an over-long prompt is REJECTED with a clear
        error instead of silently truncating its context (the old
        launch/serve.py failure mode)."""
        plen = int(len(req.prompt))
        need = plen + req.max_new_tokens
        if plen < 1:
            raise ValueError(f"request {req.req_id}: empty prompt")
        if req.req_id in self._states:
            raise ValueError(f"request id {req.req_id} already submitted")
        if need > self.geom.max_ctx:
            raise ValueError(
                f"request {req.req_id}: prompt ({plen}) + max_new_tokens "
                f"({req.max_new_tokens}) = {need} exceeds the page-table "
                f"capacity pages_per_seq * page_sz = {self.geom.max_ctx}; "
                f"raise --pages / --page-sz or split the request (context "
                f"is never silently truncated)")
        st = _ReqState(req=req, submitted_step=self.step_count,
                       submit_wall=time.perf_counter(),
                       waiting_since=self.step_count,
                       history=[int(t) for t in req.prompt])
        self._states[req.req_id] = st
        self._waiting.append(st)

    @property
    def n_active(self) -> int:
        return len(self._waiting) + len(self._running)

    # ------------------------------------------------------------------
    def _admit(self) -> None:
        from repro.core.chunking import prompt_slices
        cap = min(self.config.prefill_chunk or self.geom.cap_t,
                  self.geom.cap_t)
        ps = self.geom.page_sz
        while self._waiting:
            st = self._waiting[0]
            rid = st.req.req_id
            # resume after preemption: re-prefill everything already fed
            # (history minus the un-fed last token); fresh requests prefill
            # the prompt — and must FEED at least its last token (the TTFT
            # token comes out of it), so the prefix match stops one short
            target = st.history[:-1] if st.output \
                else [int(t) for t in st.req.prompt]
            mr = len(target) if st.output else len(target) - 1
            pages_hit, rows_hit = self.pool.match_prefix(target, mr)
            remaining = len(target) - rows_hit
            # admission gate: the first chunk (or the resume's first decode
            # write) must be able to allocate its pages, else the stream
            # would be admitted only to stall — wait (or preempt) instead
            first_rows = min(remaining, cap) if remaining else 1
            end = rows_hit + first_rows
            needed = -(-end // ps) - len(pages_hit)
            if rows_hit % ps and pages_hit:
                needed += 1      # shared partial tail: first write may COW
            # adopting a free-but-cached page RESURRECTS it off the free
            # list — it costs a free slot exactly like a fresh allocation.
            # Not charging resurrections let admission drain the whole
            # free pool into doomed prefills while a running decode stream
            # starved on one page (preemption livelock, tested).
            needed += sum(1 for p in pages_hit
                          if self.pool.refcount(p) == 0)
            if needed > self.pool.n_free and self.pool.in_use > 0:
                if self._maybe_preempt(st):
                    continue    # retry against the freed pages
                return
            self._waiting.popleft()
            self.pool.alloc_table(rid)
            if pages_hit:
                self.pool.adopt_prefix(rid, pages_hit, rows_hit)
            st.phase = "prefill"
            st.committed = rows_hit
            st.next_chunk = 0
            st.prefill_target = target
            off, st.chunks = rows_hit, []
            if remaining:
                for ln in prompt_slices(self._cm, remaining, cap):
                    st.chunks.append((off, ln))
                    off += ln
            else:
                # resume fully served by the cache: straight back to decode
                st.phase = "decode"
            self._running.append(st)

    def _preempt_stream(self, victim: _ReqState) -> None:
        """Publish then free the victim's pages and requeue it for a
        resume-prefill. Its published pages stay cached, so the resume
        typically prefix-hits most of its own history. Greedy decode is
        deterministic, so preemption can never change a request's output
        ids — only its latency (tested)."""
        rid = victim.req.req_id
        self.pool.publish_ready(rid, victim.history, victim.committed)
        self.pool.preempt(rid)
        victim.phase = "waiting"
        victim.preempted += 1
        victim.waiting_since = self.step_count
        self._running.remove(victim)
        self._waiting.append(victim)

    def _maybe_preempt(self, head: _ReqState) -> bool:
        """Page-short admission policy: once the queue's head has waited
        ``preempt_waiting_steps`` steps, evict the most recently admitted
        decode stream (its first token is already out — decode-phase
        implies progress)."""
        n = self.config.preempt_waiting_steps
        if n is None or self.step_count - head.waiting_since < n:
            return False
        victims = [s for s in self._running if s.phase == "decode"]
        if not victims:
            return False
        self._preempt_stream(victims[-1])
        return True

    # ------------------------------------------------------------------
    def _candidates(self) -> Tuple[List[Segment], List[List[Segment]]]:
        dec: List[Segment] = []
        pre: List[List[Segment]] = []
        k = self.geom.k
        for st in self._running:
            rid = st.req.req_id
            if st.phase == "decode":
                # cap the draft so the stream never writes past its own
                # page table (pos <= plen + max_new - 2 < max_ctx) and the
                # last useful token isn't padded with doomed drafts
                n_draft = max(0, min(k - 1, st.req.max_new_tokens
                                     - len(st.output) - 1))
                draft = propose_draft(st.history, n_draft,
                                      ngram=self.config.draft_ngram)
                dec.append(Segment(
                    req_id=rid, kind="decode",
                    tokens=(st.next_token, *draft),
                    base=st.committed))
            elif st.phase == "prefill":
                segs = []
                for off, ln in st.chunks[st.next_chunk:]:
                    segs.append(Segment(
                        req_id=rid, kind="prefill",
                        tokens=tuple(st.prefill_target[off:off + ln]),
                        base=off))
                pre.append(segs)
        return dec, pre

    # ------------------------------------------------------------------
    def _secure_pages(self, plan: StepPlan) -> List[Tuple[int, int]]:
        """Walk the plan in execution order and make every page each
        segment will write allocated and writable: logical page ``idx``
        already in the table goes through :meth:`PagedKVPool.
        ensure_writable` (COW pairs are returned for the device copy
        program); pages past the table are allocated on write. A segment
        whose pages cannot be secured is dropped from the plan (deferred),
        along with every later segment of the same request."""
        ps, pp = self.geom.page_sz, self.geom.pages_per_seq
        copies: List[Tuple[int, int]] = []
        dropped: set = set()
        for item in plan.items:
            kept = []
            for sg in item:
                rid = sg.req_id
                ok = rid not in dropped
                if ok:
                    table = self.pool.table_of(rid)
                    end = min(sg.start + len(sg.tokens), pp * ps)
                    for idx in range(sg.start // ps,
                                     max(end - 1, sg.start) // ps + 1):
                        if idx < len(table):
                            status, pair = self.pool.ensure_writable(
                                rid, idx)
                            if status == "fail":
                                ok = False
                                break
                            if pair is not None:
                                copies.append(pair)
                        else:
                            while ok and len(table) <= idx:
                                ok = self.pool.append_page(rid) is not None
                            if not ok:
                                break
                if ok:
                    kept.append(sg)
                else:
                    dropped.add(rid)
                    if sg.kind == "decode":
                        plan.decode_tokens -= len(sg.tokens)
                        plan.deferred_decode += 1
                    else:
                        plan.prefill_tokens -= len(sg.tokens)
                        plan.deferred_prefill += 1
            item[:] = kept
        return copies

    def _run_copies(self, copies: List[Tuple[int, int]]) -> None:
        """Execute COW page copies on device, ``copy_cap`` pairs per call
        (sentinel-padded). MUST run before this step's engine program —
        and before any preemption can recycle a source page."""
        if not copies:
            return
        import jax.numpy as jnp
        cc = self.geom.copy_cap
        sent = self.geom.trash_page
        for i in range(0, len(copies), cc):
            src = np.full((cc,), sent, np.int32)
            dst = np.full((cc,), sent, np.int32)
            for j, (s_, d_) in enumerate(copies[i:i + cc]):
                src[j], dst[j] = s_, d_
            self.pool_state = self._copy_fn(
                self.pool_state,
                {"src": jnp.asarray(src), "dst": jnp.asarray(dst)})

    def _pack(self, plan: StepPlan):
        import jax.numpy as jnp
        g = self.geom
        n, c, pp = g.n_items, g.cap_t, g.pages_per_seq
        tokens = np.zeros((n, c), np.int32)
        pos = np.zeros((n, c), np.int32)
        seg = np.full((n, c), -1, np.int32)
        base = np.zeros((n, c), np.int32)
        pages = np.full((n, c, pp), g.trash_page, np.int32)
        placements = []
        for i, item in enumerate(plan.items):
            cur = 0
            for s_idx, sg in enumerate(item):
                ln = len(sg.tokens)
                tokens[i, cur:cur + ln] = sg.tokens
                pos[i, cur:cur + ln] = np.arange(sg.start, sg.start + ln)
                seg[i, cur:cur + ln] = s_idx
                base[i, cur:cur + ln] = sg.base
                table = self.pool.table_of(sg.req_id) or []
                pages[i, cur:cur + ln, :len(table)] = \
                    np.asarray(table, np.int32)[None, :]
                placements.append((sg, i, cur))
                cur += ln
        batch = {"tokens": jnp.asarray(tokens), "pos": jnp.asarray(pos),
                 "seg": jnp.asarray(seg), "ctx_base": jnp.asarray(base),
                 "pages": jnp.asarray(pages)}
        return batch, placements

    # ------------------------------------------------------------------
    def _finish(self, st: _ReqState) -> None:
        st.phase = "done"
        st.finished_step = self.step_count
        st.done_wall = time.perf_counter()
        rid = st.req.req_id
        # a finished request's full pages stay in the prefix cache
        # (free-but-cached) — the next request sharing its prefix hits them
        self.pool.publish_ready(rid, st.history, st.committed)
        self.pool.free_table(rid)
        self._running.remove(st)
        n_out = len(st.output)
        tpot = None
        if n_out > 1:
            tpot = (st.done_wall - st.first_wall) / (n_out - 1)
        self.results[rid] = RequestResult(
            req_id=rid, prompt_len=len(st.req.prompt),
            output_ids=list(st.output),
            submitted_step=st.submitted_step,
            first_token_step=st.first_token_step,
            finished_step=st.finished_step,
            ttft_s=st.first_wall - st.submit_wall, tpot_s=tpot,
            preempted=st.preempted)

    def _emit(self, st: _ReqState, token: int,
              events: List[Tuple[int, int]]) -> bool:
        """Append one output token; returns True when the request is
        done (caller must stop consuming further tokens this step)."""
        st.output.append(int(token))
        st.history.append(int(token))
        self._emitted_total += 1
        events.append((st.req.req_id, int(token)))
        if st.first_token_step < 0:
            st.first_token_step = self.step_count
            st.first_wall = time.perf_counter()
        eos = st.req.eos_id
        if (eos is not None and token == eos) \
                or len(st.output) >= st.req.max_new_tokens:
            self._finish(st)
            return True
        st.next_token = int(token)
        return False

    # ------------------------------------------------------------------
    def step(self) -> List[Tuple[int, int]]:
        """Run one engine step; returns the (req_id, token) stream emitted
        by this step (per-request output streams in arrival order)."""
        self._admit()
        # plan + secure pages; if page exhaustion kills EVERY segment, the
        # step would spin forever — force-preempt the newest page-holding
        # stream (LIFO keeps the oldest progressing) and re-plan. COW
        # copies run immediately so a later preemption can never recycle a
        # source page before its rows are duplicated.
        guard = 4 * (len(self._running) + len(self._waiting) + 1)
        while True:
            dec_c, pre_c = self._candidates()
            plan = self.scheduler.plan(dec_c, pre_c)
            self._run_copies(self._secure_pages(plan))
            guard -= 1
            if plan.n_segments or not self._running or guard <= 0:
                break
            victims = [s for s in self._running
                       if self.pool.table_of(s.req.req_id)]
            if not victims:
                break
            self._preempt_stream(victims[-1])
            self._admit()   # freed pages may unblock the queue head
        batch, placements = self._pack(plan)
        step_fn = self.cache.get(self.bucket_key, self._build_step)
        ids, self.pool_state = step_fn(self.params, self.pool_state, batch)
        ids = np.asarray(ids)

        events: List[Tuple[int, int]] = []
        for sg, item, off in placements:
            st = self._states[sg.req_id]
            if st.phase == "done":
                continue
            out = ids[item, off:off + len(sg.tokens)]
            if sg.kind == "prefill":
                self._prefill_fed += len(sg.tokens)
                st.committed += len(sg.tokens)
                st.next_chunk += 1
                if st.committed == len(st.prefill_target):
                    st.phase = "decode"
                    if not st.output:
                        # the final chunk's last-position greedy id is the
                        # first generated token (the TTFT token)
                        self._emit(st, int(out[-1]), events)
                    # resumed prefill: next_token (the un-fed last emitted
                    # token) is already set; out[-1] re-predicts it
            else:
                emitted = verify_greedy(sg.tokens, out)
                self.spec_stats.decode_ticks += 1
                self.spec_stats.drafted += len(sg.tokens) - 1
                self.spec_stats.accepted += len(emitted) - 1
                self.spec_stats.emitted += len(emitted)
                st.committed += len(emitted)
                for tok in emitted:
                    if self._emit(st, tok, events):
                        break
        # newly completed pages enter the prefix cache as soon as their
        # rows are committed — a concurrent request can share a page with
        # its still-running publisher
        for st in list(self._running):
            self.pool.publish_ready(st.req.req_id, st.history, st.committed)
        self.pool.note_tick()
        self.step_count += 1
        self.sim_time += self.config.sim_dt
        return events

    # ------------------------------------------------------------------
    def run(self, trace: Sequence[Request], *,
            max_steps: int = 100_000) -> Dict[int, RequestResult]:
        """Drive a full trace (simulated arrival times) to completion."""
        t0 = time.perf_counter()
        pending = sorted(trace, key=lambda r: r.arrival)
        i = 0
        while (i < len(pending) or self.n_active) \
                and self.step_count < max_steps:
            while i < len(pending) and pending[i].arrival <= self.sim_time:
                try:
                    self.submit(pending[i])
                except ValueError as e:
                    # one bad request (over-long, duplicate id) must not
                    # abort the trace — record the rejection and move on
                    self.rejected[pending[i].req_id] = str(e)
                i += 1
            if not self.n_active and i < len(pending):
                # idle: fast-forward simulated time to the next arrival
                self.sim_time = pending[i].arrival
                continue
            self.step()
        self._run_wall += time.perf_counter() - t0
        if self.timeline is not None:
            st = self.stats()
            self.timeline.record(
                "engine", self.step_count, bucket=str(self.bucket_key),
                completed=st["completed"], steps=st["steps"],
                wall_s=st["wall_s"], tokens_per_s=st["tokens_per_s"],
                ttft_s_p50=st["ttft_s_p50"], ttft_s_p95=st["ttft_s_p95"],
                tpot_s_p50=st["tpot_s_p50"], tpot_s_p95=st["tpot_s_p95"],
                occupancy=st["kv_pool"].get("mean_occupancy"))
        if self.n_active:
            raise RuntimeError(
                f"trace did not drain in {max_steps} steps: "
                f"{self.n_active} requests still active")
        return self.results

    # ------------------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        res = list(self.results.values())
        ttft_s = [r.ttft_s for r in res]
        ttft_steps = [r.ttft_steps for r in res]
        # n_out < 2 has no inter-token latency: excluded EXPLICITLY (None),
        # never conflated with a measured-0 tpot
        tpot = [r.tpot_s for r in res if r.tpot_s is not None]

        def pct(xs, q):
            return float(np.percentile(xs, q)) if xs else 0.0

        wall = max(self._run_wall, 1e-9)
        return {
            "completed": len(res),
            "rejected": len(self.rejected),
            "steps": self.step_count,
            "emitted_tokens": self._emitted_total,
            "prefill_tokens_fed": self._prefill_fed,
            "tokens_per_s": round(self._emitted_total / wall, 2),
            "wall_s": round(self._run_wall, 3),
            "ttft_s_p50": round(pct(ttft_s, 50), 4),
            "ttft_s_p95": round(pct(ttft_s, 95), 4),
            "ttft_steps_p50": pct(ttft_steps, 50),
            "ttft_steps_p95": pct(ttft_steps, 95),
            "tpot_s_p50": round(pct(tpot, 50), 5),
            "tpot_s_p95": round(pct(tpot, 95), 5),
            "tpot_measured": len(tpot),
            "kv_pool": self.pool.stats.as_dict(),
            "speculative": self.spec_stats.as_dict(),
            "compile_cache": self.cache.stats.as_dict(),
        }


def _axes(mesh):
    from repro.runtime.sharding import mesh_axis_names
    return mesh_axis_names(mesh)


# ===========================================================================
# The one-shot reference path (parity oracle).
# ===========================================================================

def one_shot_generate(cfg_arch, mesh, params, prompts: Sequence[Sequence[int]],
                      max_new: int, *, cap: Optional[int] = None,
                      compute_dtype=None,
                      eos_id: Optional[int] = None) -> List[List[int]]:
    """The pre-engine one-shot serve path: each output token is produced by
    a FULL teacher-forced prefill of (prompt + generated-so-far) through
    the EPP pipeline (``pipeline_loss_fn`` mode="prefill") — no KV reuse,
    no continuous batching, one request at a time. Quadratically slow and
    exactly right: the oracle the engine's paged-cache incremental
    decode is tested against (ids must match at every k).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.models import LayerCtx
    from repro.runtime import make_geometry
    from repro.runtime.pipeline import pipeline_loss_fn
    from repro.runtime.sharding import (batch_specs, mesh_axis_names,
                                        shard_dim_tree)
    from repro.runtime.train_step import batch_struct, param_pspecs

    compute_dtype = compute_dtype or jnp.float32
    pod, data, model = mesh_axis_names(mesh)
    if pod is not None:
        raise NotImplementedError("one-shot reference runs on a "
                                  "(data, model) mesh")
    d_s = mesh.shape[model]
    need = max((len(p) for p in prompts), default=1) + max_new
    cap = max(cap or 0, need)
    cap = -(-cap // d_s) * d_s
    geom = make_geometry(cfg_arch, mesh, n_chunks=1, cap=cap, ctx_cap=cap,
                         l_ckpt=0, compute_dtype=compute_dtype)
    params_shape = jax.eval_shape(lambda: params)
    pspecs = param_pspecs(cfg_arch, params_shape, mesh)
    shard_dims = shard_dim_tree(params["stages"], d_s)
    bspecs = batch_specs(batch_struct(geom, 1), pod=None, model=model)
    if geom.policy == "ulysses":
        kspec = P(data, None, model, None)
    else:
        kspec = P(data, None, None, None)
    ctx_spec = LayerCtx(kspec, kspec, None, None)
    fn = pipeline_loss_fn(cfg_arch, geom, shard_dims, pod_axis=None,
                          data_axis=data, model_axis=model, mode="prefill")
    mapped = jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(pspecs, bspecs),
        out_specs=(P(None, model), ctx_spec), check_vma=False))

    outs: List[List[int]] = []
    for prompt in prompts:
        seq = [int(t) for t in prompt]
        gen: List[int] = []
        for _ in range(max_new):
            n = len(seq)
            if n > cap:
                raise ValueError(f"sequence length {n} exceeds cap {cap}")
            tokens = np.zeros((1, cap), np.int32)
            tokens[0, :n] = seq
            seg = np.full((1, cap), -1, np.int32)
            seg[0, :n] = 0
            pos = np.zeros((1, cap), np.int32)
            pos[0, :n] = np.arange(n)
            batch = {
                "tokens": jnp.asarray(tokens),
                "targets": jnp.asarray(np.full((1, cap), -1, np.int32)),
                "seg": jnp.asarray(seg),
                "pos": jnp.asarray(pos),
                "ctx_len": jnp.zeros((1,), jnp.int32),
            }
            ids, _ = mapped(params, batch)
            nxt = int(np.asarray(ids)[0, n - 1])
            gen.append(nxt)
            seq.append(nxt)
            if eos_id is not None and nxt == eos_id:
                break
        outs.append(gen)
    return outs
