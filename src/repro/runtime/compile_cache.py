"""First-class plan-bucket compile cache.

The planner emits a fresh :class:`~repro.core.plan.ExecutionPlan` every
step, but plans land in a small number of *buckets* — schedule backend,
chunk-count rounded up, capacity rounded to the SP degree, context capacity
rounded to the capacity (§III: "emit bucketed chunk geometry so the
compiled program is reused"). One bucket = one compiled executable; this
module owns the bucket-key -> executable mapping that used to live as
private helpers in ``launch/train.py``, and is reused by
``launch/serve.py`` and ``launch/dryrun.py``.

Deliberately jax-free: keys are plain tuples (from
``ExecutionPlan.bucket_key()`` or :func:`decode_bucket_key`) and values are
whatever the builder returns (a jit'd step, a (builder, step) pair, a
compiled lowering). Hit/miss/eviction/compile-time statistics are kept per
cache and aggregated process-wide (:func:`global_cache_stats`) so the
train-loop log, ``launch/analysis.py`` and ``benchmarks/run.py`` can all
surface them.

A cache may be backed by a persistent ``store`` (duck-typed; see
``runtime/cache_store.CacheStore`` for the disk+JAX-AOT implementation):
an in-memory miss first consults ``store.load(key)`` — success is a
**warm hit** (``CacheStats.warm_hits``, no fresh compile) — and every
fresh compile is offered to ``store.save(...)`` so the NEXT process
restart warm-starts. The store decides validity (fingerprint, integrity);
the cache only distinguishes warm hits from cold compiles.

Eviction is LRU by default; ``eviction="cost"`` weights the choice by
each resident bucket's rebuild cost (``compile_seconds_per_key``), so
cheap-to-rebuild buckets — including warm-loaded ones, whose rebuild cost
is a disk reload — are evicted first, with LRU order as the tie-break.

The process-wide registry holds caches *weakly*: a cache (and every
executable it pins) is freed with its last strong reference, so repeated
in-process train/serve runs do not leak executables through the stats
aggregation. Live-bucket count and recompile count are tracked separately —
``misses`` over-counts live buckets as soon as a bounded cache evicts and
recompiles a key — and per-key compile-second stats are pruned on eviction
so they cannot grow without bound.
"""

from __future__ import annotations

import threading
import weakref
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Hashable, Optional, Set, Tuple

from repro.telemetry.timeline import span

__all__ = ["CacheStats", "CompileCache", "decode_bucket_key",
           "engine_bucket_key", "engine_copy_bucket_key",
           "global_cache_stats", "reset_global_caches"]

# every live cache registers here (weakly) so process-wide stats can be
# aggregated without keeping dead caches — and their executables — alive
_REGISTRY: "weakref.WeakSet[CompileCache]" = weakref.WeakSet()


@dataclass
class CacheStats:
    hits: int = 0
    warm_hits: int = 0          # misses served from the persistent store
    misses: int = 0             # cold compiles (store had nothing valid)
    evictions: int = 0
    cleared: int = 0            # resident executables dropped by clear()
    recompiles: int = 0         # misses on keys that were compiled before
    buckets_live: int = 0       # executables currently resident
    compile_seconds: float = 0.0
    # program-auditor results over this cache's cold compiles (the
    # CompileCache(lint=...) hook; see src/repro/lint/)
    lint_findings: int = 0      # total findings across audited compiles
    lint_errors: int = 0        # error-severity subset
    # per-key REBUILD cost of the RESIDENT buckets (pruned on eviction):
    # compile time for cold-compiled buckets, store reload time for
    # warm-loaded ones — the weight cost-aware eviction minimizes losing
    compile_seconds_per_key: Dict[str, float] = field(default_factory=dict)

    @property
    def lookups(self) -> int:
        return self.hits + self.warm_hits + self.misses

    @property
    def hit_rate(self) -> float:
        """Fraction of lookups that avoided a fresh compile — in-memory
        hits AND store warm hits both count (misses are the cold
        compiles)."""
        if not self.lookups:
            return 0.0
        return (self.hits + self.warm_hits) / self.lookups

    def as_dict(self) -> Dict[str, Any]:
        return {
            "buckets_live": self.buckets_live,
            "recompiles": self.recompiles,
            "hits": self.hits,
            "warm_hits": self.warm_hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "cleared": self.cleared,
            "hit_rate": round(self.hit_rate, 4),
            "compile_seconds": round(self.compile_seconds, 3),
            "lint_findings": self.lint_findings,
            "lint_errors": self.lint_errors,
        }

    def summary(self) -> str:
        return (f"buckets={self.buckets_live} hits={self.hits} "
                f"warm_hits={self.warm_hits} "
                f"hit_rate={self.hit_rate:.2%} "
                f"evictions={self.evictions} "
                f"recompiles={self.recompiles} "
                f"compile_s={self.compile_seconds:.2f} "
                f"lint_findings={self.lint_findings}")


class CompileCache:
    """LRU cache from bucket key -> compiled artifact, with stats.

    ``capacity=None`` means unbounded (the train loop's default — bucket
    geometry converges to a handful of keys). A bounded cache evicts the
    least-recently-used executable (``eviction="lru"``) or the
    cheapest-to-rebuild one (``eviction="cost"``); XLA garbage-collects
    the executable with its last reference.

    ``store`` (optional) is a persistent backend with ``load(key) ->
    value | None`` and ``save(key, value, compile_seconds=...)`` — see
    ``runtime/cache_store.CacheStore``.

    ``lint`` (optional) is the program-auditor hook (``repro.lint
    .make_cache_lint``): called as ``lint(key, value)`` on every COLD
    compile, before the artifact enters the cache or the store. It
    returns a report whose finding counts land in ``CacheStats``
    (``lint_findings``/``lint_errors``) — or raises ``LintError`` in
    ``--lint error`` mode, in which case the hazardous executable is
    neither cached nor persisted. Hits and store warm-starts are never
    re-audited: a bucket is linted once, when it is born.
    """

    _COMPILED_KEYS_CAP = 65536

    def __init__(self, name: str = "default",
                 capacity: Optional[int] = None,
                 log: Optional[Callable[[str], None]] = None,
                 store: Optional[Any] = None,
                 eviction: str = "lru",
                 lint: Optional[Callable[[Hashable, Any], Any]] = None):
        if capacity is not None and capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if eviction not in ("lru", "cost"):
            raise ValueError(f"eviction must be 'lru' or 'cost', "
                             f"got {eviction!r}")
        self.name = name
        self.capacity = capacity
        self.log = log
        self.store = store
        self.eviction = eviction
        self.lint = lint
        self.stats = CacheStats()
        self._entries: "OrderedDict[Hashable, Any]" = OrderedDict()
        self._compiled_keys: Set[Hashable] = set()
        # dict/stats mutations are lock-protected so a background
        # precompile (telemetry/replan.py warming a fresh bucket
        # off-thread) can share the cache with the training loop; builds
        # and store I/O run OUTSIDE the lock — a hit never waits on a
        # concurrent compile
        self._lock = threading.RLock()
        _REGISTRY.add(self)

    # ------------------------------------------------------------------
    def __contains__(self, key: Hashable) -> bool:
        return key in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    def keys(self) -> Tuple[Hashable, ...]:
        return tuple(self._entries.keys())

    # ------------------------------------------------------------------
    def _evict_victim(self) -> Hashable:
        """Pick the entry to drop: LRU, or under ``eviction="cost"`` the
        cheapest-to-rebuild resident bucket (LRU order breaks ties). The
        most-recently-inserted entry is never the victim."""
        keys = list(self._entries.keys())
        candidates = keys[:-1] if len(keys) > 1 else keys
        if self.eviction == "cost":
            per_key = self.stats.compile_seconds_per_key
            return min(enumerate(candidates),
                       key=lambda ik: (per_key.get(repr(ik[1]), 0.0),
                                       ik[0]))[1]
        return candidates[0]

    def _enforce_capacity(self) -> None:
        if self.capacity is None:
            return
        while len(self._entries) > self.capacity:
            victim = self._evict_victim()
            del self._entries[victim]
            self.stats.evictions += 1
            self.stats.compile_seconds_per_key.pop(repr(victim), None)
            if self.log:
                self.log(f"[compile:{self.name}] evict {victim}")

    # ------------------------------------------------------------------
    def get(self, key: Hashable, build: Callable[[], Any]) -> Any:
        """Return the cached artifact for ``key``: resident -> hit;
        otherwise try the persistent store (warm hit, no compile);
        otherwise ``build()`` (cold compile, timed, offered to the
        store). Safe to call from a background thread concurrently with
        the training loop: builds and store I/O happen outside the lock,
        so a resident hit never waits on another thread's compile (two
        threads cold-building the SAME key may both compile; the first
        insert wins)."""
        with self._lock:
            if key in self._entries:
                self.stats.hits += 1
                self._entries.move_to_end(key)
                return self._entries[key]

        if self.store is not None:
            with span("repro.store_load", bucket=str(key)) as sp:
                value = self.store.load(key)
            if value is not None:
                dt = sp.seconds
                with self._lock:
                    if key in self._entries:  # raced with another loader
                        self.stats.hits += 1
                        self._entries.move_to_end(key)
                        return self._entries[key]
                    self.stats.warm_hits += 1
                    # rebuild cost of a warm bucket is a disk reload
                    self.stats.compile_seconds_per_key[repr(key)] = \
                        round(dt, 3)
                    if len(self._compiled_keys) < self._COMPILED_KEYS_CAP:
                        # a later cold rebuild of this key (evicted AND its
                        # store entry gone) must still count as a recompile
                        self._compiled_keys.add(key)
                    self._entries[key] = value
                    self._enforce_capacity()
                    self.stats.buckets_live = len(self._entries)
                if self.log:
                    self.log(f"[compile:{self.name}] warm-start bucket "
                             f"{key} ({dt:.2f}s load, no compile)")
                return value

        with self._lock:
            if key in self._entries:  # raced during the store probe
                self.stats.hits += 1
                self._entries.move_to_end(key)
                return self._entries[key]
            self.stats.misses += 1
            if key in self._compiled_keys:
                self.stats.recompiles += 1
            elif len(self._compiled_keys) < self._COMPILED_KEYS_CAP:
                # bounded recompile tracking: beyond the cap (far past any
                # real bucket churn) new keys go uncounted rather than
                # growing this set for the life of the cache — recompiles
                # become a lower bound instead of a leak
                self._compiled_keys.add(key)
        with span("repro.compile", bucket=str(key)) as sp:
            value = build()
        dt = sp.seconds
        if self.lint is not None:
            # audit the newborn program BEFORE it becomes reusable state:
            # in error mode the hook raises and the executable is neither
            # cached nor persisted
            report = self.lint(key, value)
            if report is not None:
                with self._lock:
                    n = len(report.findings)
                    self.stats.lint_findings += n
                    self.stats.lint_errors += len(report.errors)
                if n and self.log:
                    self.log(f"[compile:{self.name}] lint: "
                             f"{report.summary()}")
        with self._lock:
            self.stats.compile_seconds += dt
            self.stats.compile_seconds_per_key[repr(key)] = round(dt, 3)
            self._entries[key] = value
            self._enforce_capacity()
            self.stats.buckets_live = len(self._entries)
        if self.log:
            self.log(f"[compile:{self.name}] bucket {key} ({dt:.2f}s)")
        if self.store is not None:
            self.store.save(key, value, compile_seconds=dt)
        return value

    def clear(self, reset_stats: bool = False) -> None:
        """Drop every resident executable — observably: the number of
        entries dropped is added to ``stats.cleared`` so a later
        ``global_cache_stats()`` read accounts for where the resident
        executables went. ``reset_stats=True`` also zeroes the counters
        and the compiled-key history (a fresh run in the same process);
        otherwise hit/miss history survives — including which keys were
        compiled before, so a post-clear rebuild still counts as a
        recompile."""
        with self._lock:
            dropped = len(self._entries)
            self._entries.clear()
            if reset_stats:
                self._compiled_keys.clear()
                self.stats = CacheStats()
                return
            self.stats.cleared += dropped
            self.stats.buckets_live = 0
            self.stats.compile_seconds_per_key.clear()
        if dropped and self.log:
            self.log(f"[compile:{self.name}] cleared {dropped} "
                     f"resident executables")

    def deregister(self) -> None:
        """Remove this cache from the process-wide stats registry (it keeps
        working as a plain cache). The weak registry already drops a cache
        with its last reference; this is for module-global caches that
        should stop contributing to :func:`global_cache_stats` early."""
        _REGISTRY.discard(self)


def decode_bucket_key(geom) -> Tuple:
    """Bucket key for a pipelined-decode executable: the static decode
    geometry (one compiled program per (batch, cache-length) bucket).
    ``cache_len`` and the compute dtype are both part of executable
    identity — a decode step compiled for one context size must never be
    handed a state of another."""
    return ("decode", geom.batch_per_pod, geom.cache_len, geom.d_p,
            geom.d_s, geom.n_micro, getattr(geom, "dtype_name", "bfloat16"))


def engine_bucket_key(geom) -> Tuple:
    """Bucket key for a serving-engine step executable. The engine's whole
    point is that this set is CLOSED: per-request lengths are data, so one
    (items, cap_t, pages, page_sz, pages_per_seq, k) geometry serves every
    request mix and the second pass over any trace compiles nothing."""
    return ("engine", geom.n_items, geom.cap_t, geom.n_pages, geom.page_sz,
            geom.pages_per_seq, geom.k, geom.d_p, geom.d_s, geom.dtype_name)


def engine_copy_bucket_key(geom) -> Tuple:
    """Bucket key for the engine's copy-on-write page-copy executable —
    the second (and last) member of the closed serve bucket set. Identity
    is the pool layout plus ``copy_cap`` (pairs per call)."""
    return ("engine-copy", geom.n_pages, geom.page_sz, geom.copy_cap,
            geom.d_p, geom.d_s, geom.dtype_name)


def global_cache_stats() -> Dict[str, Any]:
    """Aggregate stats over every LIVE cache in this process, plus the
    per-cache breakdown — the shape benchmarks/run.py emits as JSON.
    Caches with a persistent store also report the store block
    (entries, size, stale/corrupt skips)."""
    agg = CacheStats()
    per_cache = {}
    for c in list(_REGISTRY):
        agg.hits += c.stats.hits
        agg.warm_hits += c.stats.warm_hits
        agg.misses += c.stats.misses
        agg.evictions += c.stats.evictions
        agg.cleared += c.stats.cleared
        agg.recompiles += c.stats.recompiles
        agg.buckets_live += c.stats.buckets_live
        agg.compile_seconds += c.stats.compile_seconds
        agg.lint_findings += c.stats.lint_findings
        agg.lint_errors += c.stats.lint_errors
        d = c.stats.as_dict()
        if c.store is not None and hasattr(c.store, "report"):
            d["store"] = c.store.report()
        per_cache[c.name] = d
    out = agg.as_dict()
    out["caches"] = per_cache
    return out


def reset_global_caches() -> None:
    """Drop the registry (tests; a fresh train run in the same process)."""
    _REGISTRY.clear()
