"""Persistent compile-cache store: warm-start round-trip across simulated
process restarts, fingerprint invalidation, corruption fallback, and the
warm-hit / cost-aware-eviction accounting in CompileCache.

The store contract under test (runtime/cache_store.py):
* a fresh cache in a "new process" warm-loads a persisted executable and
  produces BITWISE-identical outputs to the cold compile;
* a stale fingerprint (topology/config change) or a corrupted payload is
  SKIPPED — cold compile fallback, never a wrong load, never a crash.
"""

import json
import pickle

import numpy as np
import pytest

from repro.runtime.cache_store import (CacheStore, model_fingerprint,
                                       store_fingerprint)
from repro.runtime.compile_cache import CompileCache, global_cache_stats, \
    reset_global_caches


# ---------------------------------------------------------------------------
# jax-free unit tests: store bookkeeping + CompileCache integration
# ---------------------------------------------------------------------------

class _FakeStore:
    """In-memory stand-in implementing the CompileCache store protocol."""

    def __init__(self, preload=None):
        self.blobs = dict(preload or {})
        self.saved = {}

    def load(self, key):
        return self.blobs.get(key)

    def save(self, key, value, *, compile_seconds=0.0):
        self.saved[key] = (value, compile_seconds)
        self.blobs[key] = value
        return True


def test_warm_hit_accounting():
    """A store hit is a warm hit — not a plain hit, not a cold compile —
    and the build callable must NOT run."""
    cache = CompileCache(name="warm", store=_FakeStore({("k",): "warm!"}))
    built = []
    v = cache.get(("k",), lambda: built.append(1) or "cold")
    assert v == "warm!" and built == []
    s = cache.stats
    assert (s.warm_hits, s.misses, s.hits) == (1, 0, 0)
    assert s.lookups == 1
    assert s.compile_seconds == 0.0
    # now resident: second lookup is a plain in-memory hit
    assert cache.get(("k",), lambda: "cold") == "warm!"
    assert cache.stats.hits == 1
    d = s.as_dict()
    assert d["warm_hits"] == 1 and "warm_hits" in s.summary()


def test_cold_compile_offered_to_store():
    store = _FakeStore()
    cache = CompileCache(name="offer", store=store)
    cache.get("key", lambda: "artifact")
    assert store.saved["key"][0] == "artifact"
    assert cache.stats.misses == 1 and cache.stats.warm_hits == 0


def test_cost_aware_eviction_drops_cheap_buckets_first():
    cache = CompileCache(name="cost", capacity=2, eviction="cost")
    cache.get("slow", lambda: "s")
    cache.get("fast", lambda: "f")
    # make the recorded rebuild costs unambiguous
    cache.stats.compile_seconds_per_key[repr("slow")] = 30.0
    cache.stats.compile_seconds_per_key[repr("fast")] = 0.1
    cache.get("new", lambda: "n")
    # plain LRU would evict "slow" (oldest); cost-aware keeps it
    assert "slow" in cache and "new" in cache and "fast" not in cache
    assert cache.stats.evictions == 1
    assert repr("fast") not in cache.stats.compile_seconds_per_key


def test_cost_eviction_never_drops_just_inserted_entry():
    cache = CompileCache(name="cost2", capacity=1, eviction="cost")
    cache.get("a", lambda: "a")
    cache.stats.compile_seconds_per_key[repr("a")] = 100.0
    cache.get("b", lambda: "b")  # b is newest: a must go despite its cost
    assert "b" in cache and "a" not in cache


def test_clear_is_observable_in_stats():
    """clear(reset_stats=False) must not make resident executables vanish
    invisibly: the dropped count lands in ``cleared`` and flows through
    as_dict + global_cache_stats."""
    reset_global_caches()
    cache = CompileCache(name="clear-obs")
    cache.get(1, lambda: "x")
    cache.get(2, lambda: "y")
    cache.clear()
    assert cache.stats.cleared == 2
    assert cache.stats.buckets_live == 0
    assert cache.stats.compile_seconds_per_key == {}
    d = cache.stats.as_dict()
    assert d["cleared"] == 2
    g = global_cache_stats()
    assert g["cleared"] == 2 and g["caches"]["clear-obs"]["cleared"] == 2
    # a second clear with nothing resident adds nothing
    cache.clear()
    assert cache.stats.cleared == 2
    # reset_stats zeroes the counter with everything else
    cache.get(3, lambda: "z")
    cache.clear(reset_stats=True)
    assert cache.stats.cleared == 0


def test_model_fingerprint_tracks_spec_fields():
    from repro.core import ModelSpec
    a = ModelSpec(name="t", n_layers=4, d_model=64, n_heads=4, n_kv_heads=2,
                  head_dim=16, d_ff=128, vocab=64)
    b = ModelSpec(name="t", n_layers=4, d_model=64, n_heads=4, n_kv_heads=2,
                  head_dim=16, d_ff=128, vocab=64)
    c = ModelSpec(name="t", n_layers=8, d_model=64, n_heads=4, n_kv_heads=2,
                  head_dim=16, d_ff=128, vocab=64)
    assert model_fingerprint(a) == model_fingerprint(b)
    assert model_fingerprint(a) != model_fingerprint(c)


def test_store_save_of_unserializable_artifact_degrades(tmp_path):
    """A jit wrapper (not a Compiled) or a plain value cannot be
    serialized: save must return False and count, never raise."""
    store = CacheStore(tmp_path, {"v": 1})
    ok = store.save(("k",), object())
    assert not ok
    assert store.stats.save_errors == 1
    assert store.load(("k",)) is None
    assert store.report()["entries"] == 0


# ---------------------------------------------------------------------------
# jax round-trip tests: serialize -> "new process" -> deserialize
# ---------------------------------------------------------------------------

def _compile_toy_step(scale: float):
    """A tiny AOT-compiled jit step standing in for a bucket executable."""
    import jax
    import jax.numpy as jnp

    def step(x):
        return jnp.tanh(x * scale) @ jnp.full((8, 8), scale, jnp.float32)

    x_abs = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    return jax.jit(step).lower(x_abs).compile()


def _toy_input():
    import jax.numpy as jnp
    return jnp.linspace(-2.0, 2.0, 64, dtype=jnp.float32).reshape(8, 8)


def test_warm_start_round_trip_bitwise_identical(tmp_path):
    """Populate the store in "process 1"; a fresh CompileCache + CacheStore
    in "process 2" must warm-load (0 fresh compiles) and produce output
    bitwise-identical to the cold compile."""
    fp = store_fingerprint()
    key = ("bucket", 8, 128)

    # --- process 1: cold compile, persisted ---
    store1 = CacheStore(tmp_path, fp)
    cache1 = CompileCache(name="proc1", store=store1)
    compiled1 = cache1.get(key, lambda: _compile_toy_step(0.5))
    assert cache1.stats.misses == 1 and store1.stats.saves == 1
    cold_out = np.asarray(compiled1(_toy_input()))

    # --- process 2: fresh cache + store objects over the same directory ---
    store2 = CacheStore(tmp_path, store_fingerprint())
    cache2 = CompileCache(name="proc2", store=store2)
    built = []
    compiled2 = cache2.get(key, lambda: built.append(1) or
                           _compile_toy_step(0.5))
    assert built == [], "warm start must not compile"
    assert cache2.stats.warm_hits == 1 and cache2.stats.misses == 0
    assert cache2.stats.compile_seconds == 0.0
    warm_out = np.asarray(compiled2(_toy_input()))
    assert cold_out.tobytes() == warm_out.tobytes()


def test_stale_fingerprint_skipped_with_cold_fallback(tmp_path):
    """A topology change (different fingerprint) must not load the old
    entry: stale skip + cold compile, and the old entry survives for a
    process that returns to the original topology (elastic grow-back)."""
    fp_a = store_fingerprint(extra={"mesh": [["data", 2], ["model", 2]]})
    fp_b = store_fingerprint(extra={"mesh": [["data", 1], ["model", 2]]})
    key = ("bucket", 1)

    store_a = CacheStore(tmp_path, fp_a)
    CompileCache(name="a", store=store_a).get(
        key, lambda: _compile_toy_step(1.0))
    assert store_a.stats.saves == 1

    store_b = CacheStore(tmp_path, fp_b)
    cache_b = CompileCache(name="b", store=store_b)
    built = []
    cache_b.get(key, lambda: built.append(1) or _compile_toy_step(2.0))
    assert built == [1], "stale entry must cold compile"
    assert store_b.stats.stale_skips == 1
    assert cache_b.stats.warm_hits == 0 and cache_b.stats.misses == 1

    # both topologies' entries now coexist; returning to fp_a warm-starts
    store_a2 = CacheStore(tmp_path, fp_a)
    cache_a2 = CompileCache(name="a2", store=store_a2)
    cache_a2.get(key, lambda: pytest.fail("should warm-start"))
    assert cache_a2.stats.warm_hits == 1
    assert store_a2.report()["entries"] == 2


def test_fingerprint_with_non_json_native_values_round_trips(tmp_path):
    """Tuples and arbitrary objects in the fingerprint must not (a) crash
    save()'s sidecar dump or (b) read back permanently stale because the
    JSON round-trip changed their representation — the fingerprint is
    canonicalized once at construction."""
    class Odd:
        def __str__(self):
            return "odd-value"

    fp = {"mesh": (("data", 2), ("model", 2)), "dtype": Odd()}
    key = ("bucket", 9)
    store1 = CacheStore(tmp_path, fp)
    cache1 = CompileCache(name="nj1", store=store1)
    cache1.get(key, lambda: _compile_toy_step(0.9))
    assert store1.stats.saves == 1 and store1.stats.save_errors == 0

    # "new process": an equal-but-distinct fingerprint object
    store2 = CacheStore(tmp_path, {"mesh": (("data", 2), ("model", 2)),
                                   "dtype": Odd()})
    cache2 = CompileCache(name="nj2", store=store2)
    cache2.get(key, lambda: pytest.fail("should warm-start"))
    assert cache2.stats.warm_hits == 1
    assert store2.stats.stale_skips == 0


def test_corrupted_payload_skipped_with_cold_fallback(tmp_path):
    fp = store_fingerprint()
    key = ("bucket", 2)
    store1 = CacheStore(tmp_path, fp)
    CompileCache(name="c1", store=store1).get(
        key, lambda: _compile_toy_step(1.5))
    (bin_path,) = tmp_path.glob("*.bin")
    bin_path.write_bytes(bin_path.read_bytes()[:-16] + b"garbagegarbage!!")

    store2 = CacheStore(tmp_path, fp)
    cache2 = CompileCache(name="c2", store=store2)
    built = []
    out = cache2.get(key, lambda: built.append(1) or _compile_toy_step(1.5))
    assert built == [1], "corrupted entry must cold compile"
    assert store2.stats.corrupt_skips == 1
    assert cache2.stats.misses == 1 and cache2.stats.warm_hits == 0
    # the fallback still works as an executable
    assert np.isfinite(np.asarray(out(_toy_input()))).all()


def test_unreadable_sidecar_skipped(tmp_path):
    fp = store_fingerprint()
    key = ("bucket", 3)
    store1 = CacheStore(tmp_path, fp)
    CompileCache(name="s1", store=store1).get(
        key, lambda: _compile_toy_step(0.3))
    (meta_path,) = tmp_path.glob("*.meta.json")
    meta_path.write_text("{not json")
    store2 = CacheStore(tmp_path, fp)
    assert store2.load(key) is None
    assert store2.stats.corrupt_skips == 1


def test_undeserializable_blob_counts_load_error(tmp_path):
    """A well-formed entry whose payload is not a serialized executable
    (e.g. written by a different library version) falls back cleanly."""
    fp = store_fingerprint()
    key = ("bucket", 4)
    store = CacheStore(tmp_path, fp)
    # hand-craft an entry whose sha checks out but whose pickle payload
    # is not a (payload, in_tree, out_tree) triple
    blob = pickle.dumps("not an executable")
    bin_path, meta_path = store._paths(key)
    bin_path.write_bytes(blob)
    import hashlib
    meta_path.write_text(json.dumps({
        "fingerprint": fp, "key": repr(key),
        "payload_sha": hashlib.sha256(blob).hexdigest(),
        "payload_bytes": len(blob), "compile_seconds": 0, "created": 0}))
    assert store.load(key) is None
    assert store.stats.load_errors == 1


def test_global_stats_carry_store_report(tmp_path):
    reset_global_caches()
    store = CacheStore(tmp_path, store_fingerprint())
    cache = CompileCache(name="with-store", store=store)
    cache.get(("k",), lambda: _compile_toy_step(0.7))
    g = global_cache_stats()
    blk = g["caches"]["with-store"]["store"]
    assert blk["entries"] == 1 and blk["saves"] == 1
    assert blk["size_bytes"] > 0
    assert blk["entries_current_fingerprint"] == 1


# ---------------------------------------------------------------------------
# end-to-end: a second train() run against a populated cache dir compiles
# 0 fresh executables and reproduces the cold run's losses bitwise
# ---------------------------------------------------------------------------

def test_train_warm_start_end_to_end(tmp_path):
    from repro.configs import get_arch
    from repro.launch.mesh import make_mesh
    from repro.launch.train import TrainLoopConfig, train

    cfg = get_arch("gemma3-1b").reduced()
    mesh = make_mesh((1, 1), ("data", "model"))
    mk = lambda: TrainLoopConfig(steps=2, global_batch=2, context=128,
                                 cache_dir=str(tmp_path / "cc"),
                                 compute_dtype="float32")

    _, _, hist_cold = train(cfg, mesh, mk(), log=lambda *_: None)
    cc = hist_cold[-1]["compile_cache"]
    assert cc["misses"] >= 1 and cc["warm_hits"] == 0
    assert hist_cold[-1]["cache_store"]["saves"] >= 1

    _, _, hist_warm = train(cfg, mesh, mk(), log=lambda *_: None)
    cc = hist_warm[-1]["compile_cache"]
    assert cc["misses"] == 0, f"warm run recompiled: {cc}"
    assert cc["warm_hits"] >= 1
    assert cc["compile_seconds"] == 0.0
    # warm-loaded executables reproduce the cold run bitwise
    cold = [(h["step"], h["loss"]) for h in hist_cold]
    warm = [(h["step"], h["loss"]) for h in hist_warm]
    assert cold == warm


# ---------------------------------------------------------------------------
# Bucket-key collision regression: remat vectors are part of the compiled
# step's identity — two plans that differ ONLY in their checkpointing
# vector must land in different buckets and different store entries.
# ---------------------------------------------------------------------------

def _plans_differing_only_in_ckpt():
    import copy

    from repro.core import ClusterSpec, CostModel, ModelSpec, \
        PlannerConfig, plan_batch

    m = ModelSpec(name="t", n_layers=16, d_model=1024, n_heads=16,
                  n_kv_heads=8, head_dim=64, d_ff=4096, vocab=32000)
    cm = CostModel(m, ClusterSpec(d_p=4, d_s=4, hbm_bytes=16e9))
    lengths = [65536, 30000, 8000, 8000, 4000, 2000, 1000, 500]
    plan_a = plan_batch(cm, lengths, PlannerConfig(
        bucket_rounding=64, remat_mode="stage_aware",
        capacity_bytes=cm.cluster.hbm_bytes * 0.1))
    assert plan_a.uniform_ckpt() > 0, "fixture must force checkpointing"
    plan_b = copy.deepcopy(plan_a)
    # same chunks, same schedule, same geometry — one remat entry moved
    tab = plan_b.pipelines[0].ckpt
    p, k = next((p, k) for p in range(len(tab))
                for k in range(len(tab[p])) if tab[p][k] > 0)
    tab[p][k] -= 1
    return plan_a, plan_b


def test_bucket_key_distinguishes_ckpt_vectors():
    plan_a, plan_b = _plans_differing_only_in_ckpt()
    ka, kb = plan_a.bucket_key(4), plan_b.bucket_key(4)
    # identical geometry/schedule tail ...
    assert ka._replace(ckpt="", l_ckpt=0) == kb._replace(ckpt="", l_ckpt=0)
    # ... but distinct remat digests => distinct bucket identities
    assert ka.ckpt != kb.ckpt
    assert ka != kb
    # and a CompileCache treats them as separate buckets (no false hit)
    cache = CompileCache(name="ckpt-buckets")
    assert cache.get(ka, lambda: "A") == "A"
    assert cache.get(kb, lambda: "B") == "B"
    assert cache.stats.hits == 0 and cache.stats.misses == 2


def test_cache_store_keeps_ckpt_vectors_apart(tmp_path):
    """No warm-hit on a wrong-remat executable: entries persisted under
    the two keys coexist on disk, and each key loads back exactly its own
    executable (distinguishable outputs prove which one ran)."""
    plan_a, plan_b = _plans_differing_only_in_ckpt()
    ka, kb = plan_a.bucket_key(4), plan_b.bucket_key(4)
    fp = store_fingerprint()

    store1 = CacheStore(tmp_path, fp)
    cache1 = CompileCache(name="ckpt-proc1", store=store1)
    out_a = np.asarray(cache1.get(
        ka, lambda: _compile_toy_step(0.5))(_toy_input()))
    out_b = np.asarray(cache1.get(
        kb, lambda: _compile_toy_step(2.0))(_toy_input()))
    assert store1.stats.saves == 2
    assert len(list(tmp_path.glob("*.bin"))) == 2, \
        "ckpt-vector variants must not overwrite each other's entries"
    assert out_a.tobytes() != out_b.tobytes()

    # "restart": each key warm-loads its OWN executable
    store2 = CacheStore(tmp_path, store_fingerprint())
    cache2 = CompileCache(name="ckpt-proc2", store=store2)
    warm_a = np.asarray(cache2.get(
        ka, lambda: pytest.fail("must warm-load"))(_toy_input()))
    warm_b = np.asarray(cache2.get(
        kb, lambda: pytest.fail("must warm-load"))(_toy_input()))
    assert cache2.stats.warm_hits == 2 and cache2.stats.misses == 0
    assert warm_a.tobytes() == out_a.tobytes()
    assert warm_b.tobytes() == out_b.tobytes()


# ---------------------------------------------------------------------------
# gc(): age / size-budget eviction, least-recently-loaded first
# ---------------------------------------------------------------------------

def test_gc_noop_without_limits(tmp_path):
    store = CacheStore(tmp_path, {"v": 1})
    CompileCache(name="gc0", store=store).get(
        ("k",), lambda: _compile_toy_step(1.0))
    rep = store.gc()
    assert rep["removed"] == 0 and store.report()["entries"] == 1
    assert store.stats.gc_removed == 0


def test_gc_age_evicts_old_entries_only(tmp_path):
    import os
    import time

    store = CacheStore(tmp_path, {"v": 1})
    cache = CompileCache(name="gca", store=store)
    cache.get(("old",), lambda: _compile_toy_step(1.0))
    cache.get(("new",), lambda: _compile_toy_step(2.0))
    # age the first entry far past the cutoff
    old_bin = next(p for p in tmp_path.glob("*.bin")
                   if json.loads(p.with_name(
                       p.name[:-4] + ".meta.json").read_text())["key"]
                   == repr(("old",)))
    past = time.time() - 1000
    os.utime(old_bin, (past, past))
    rep = store.gc(max_age_s=100)
    assert rep["removed"] == 1
    assert store.stats.gc_removed == 1 and store.stats.gc_removed_bytes > 0
    # the aged entry is a plain miss now; the fresh one still loads
    fresh = CompileCache(name="gca2", store=CacheStore(tmp_path, {"v": 1}))
    built = []
    fresh.get(("old",), lambda: built.append(1) or _compile_toy_step(1.0))
    fresh.get(("new",), lambda: built.append(2) or _compile_toy_step(2.0))
    assert built == [1], "old must cold-compile, new must warm-start"


def test_gc_size_budget_keeps_recently_loaded(tmp_path):
    import os
    import time

    store = CacheStore(tmp_path, {"v": 1})
    cache = CompileCache(name="gcs", store=store)
    cache.get(("a",), lambda: _compile_toy_step(1.0))
    cache.get(("b",), lambda: _compile_toy_step(2.0))
    # stamp distinct mtimes, then LOAD "a" through a fresh store — the
    # load-touch must protect it from the size-budget eviction
    for i, p in enumerate(sorted(tmp_path.glob("*.bin"))):
        os.utime(p, (time.time() - 500 + i, time.time() - 500 + i))
    store2 = CacheStore(tmp_path, {"v": 1})
    assert CompileCache(name="gcs2", store=store2).get(
        ("a",), lambda: pytest.fail("should warm-start")) is not None
    one_entry = max(p.stat().st_size for p in tmp_path.glob("*.bin"))
    rep = store2.gc(max_bytes=one_entry)
    assert rep["removed"] == 1
    assert rep["remaining_bytes"] <= one_entry
    # survivor is the recently-loaded "a"
    store3 = CacheStore(tmp_path, {"v": 1})
    cache3 = CompileCache(name="gcs3", store=store3)
    built = []
    cache3.get(("a",), lambda: built.append("a") or _compile_toy_step(1.0))
    cache3.get(("b",), lambda: built.append("b") or _compile_toy_step(2.0))
    assert built == ["b"], built


def test_gc_removal_is_miss_not_stale(tmp_path):
    """A gc'd entry must read as a plain miss — not a misleading stale or
    corrupt skip (the .bin goes first, orphan sidecars are ignored)."""
    store = CacheStore(tmp_path, {"v": 1})
    CompileCache(name="gcm", store=store).get(
        ("k",), lambda: _compile_toy_step(1.0))
    assert store.gc(max_age_s=0)["removed"] == 1
    store2 = CacheStore(tmp_path, {"v": 1})
    assert store2.load(("k",)) is None
    assert store2.stats.stale_skips == 0 and store2.stats.corrupt_skips == 0
