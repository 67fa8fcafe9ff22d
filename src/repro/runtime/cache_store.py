"""Disk-backed persistence for the plan-bucket compile cache.

The in-memory :class:`~repro.runtime.compile_cache.CompileCache` only pays
off while the process lives: every restart — a crash, a multi-run sweep,
and especially the elastic shrink/grow flow the paper motivates —
recompiles every plan bucket from scratch, and recompilation dominates
bootstrap cost for variable-length workloads that touch many buckets.
This module persists compiled executables across restarts via JAX AOT
(``jit(...).lower(...).compile()`` + ``jax.experimental
.serialize_executable``), so a restarted run warm-starts every bucket
whose environment survived the restart and cold-compiles only the rest.

Store layout (one directory, flat, one entry per (key, fingerprint)):

    <cache_dir>/
      <key_hash>__<fp_hash>.bin        pickle of (payload, in_tree,
                                       out_tree) as returned by
                                       serialize_executable.serialize()
      <key_hash>__<fp_hash>.meta.json  sidecar: the full fingerprint dict,
                                       repr(key), compile_seconds of the
                                       original build, payload sha256 +
                                       byte size, created timestamp

``key_hash`` is a sha256 over ``repr(key)`` — any hashable/repr-stable
key works (``ExecutionPlan.bucket_key()`` NamedTuples, decode geometry
tuples, dry-run cell tuples). ``fp_hash`` hashes the fingerprint dict:
entries for the SAME bucket under DIFFERENT topologies coexist (the
elastic shrink/grow flow writes both; growing back finds the original
entry intact).

Invalidation rules — a stale entry is SKIPPED, never loaded wrong:

* **fingerprint mismatch**: every entry records the store's fingerprint
  (mesh axes+shape, device count, backend platform, jax version, ModelSpec
  hash, compute dtype, a hash of the program's source — see
  :func:`store_fingerprint`). ``load`` compares
  the entry's recorded fingerprint against the current store's, field by
  field; any difference (e.g. the elastic demo's mesh change) counts as a
  ``stale_skips`` and falls back to cold compile.
* **corruption**: the sidecar records the payload's sha256; a truncated
  or bit-flipped blob (and any deserialization error) counts as a
  ``corrupt_skips`` and falls back to cold compile.
* a ``.bin`` without a readable sidecar (or vice versa) is ignored.

Writes are atomic (tmp file + ``os.replace``) so a crash mid-save leaves
no half-written entry that a later run could trip over.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import os
import pickle
import time
from pathlib import Path
from typing import Any, Callable, Dict, Hashable, List, Optional

__all__ = ["CacheStore", "StoreStats", "model_fingerprint",
           "source_fingerprint", "store_fingerprint"]

_FORMAT_VERSION = 1


def model_fingerprint(spec) -> str:
    """Stable hash of a :class:`~repro.core.plan.ModelSpec` (or any
    dataclass): two runs agree iff every architecture field agrees."""
    if dataclasses.is_dataclass(spec):
        d = dataclasses.asdict(spec)
    elif isinstance(spec, dict):
        d = spec
    else:
        d = {"repr": repr(spec)}
    blob = json.dumps(d, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


@functools.lru_cache(maxsize=None)
def source_fingerprint(package_dir: Optional[str] = None) -> str:
    """Hash of every ``.py`` file under ``package_dir`` (default: the
    ``repro`` package), paths and bytes: an executable compiled by other
    code — another commit, a patched checkout — reads as stale even where
    the bucket key and topology agree (its program differs, if only in
    its op metadata)."""
    root = Path(package_dir or Path(__file__).resolve().parents[1])
    h = hashlib.sha256()
    for path in sorted(root.rglob("*.py")):
        h.update(path.relative_to(root).as_posix().encode() + b"\0")
        h.update(path.read_bytes() + b"\0")
    return h.hexdigest()[:16]


def store_fingerprint(mesh=None, *, spec=None, compute_dtype=None,
                      extra: Optional[Dict[str, Any]] = None
                      ) -> Dict[str, Any]:
    """The topology/config fingerprint a store entry must match to load.

    Captures everything that changes the compiled HLO *outside* the bucket
    key: mesh axes and shape (an elastic reshard invalidates), total device
    count, backend platform, jax/jaxlib versions (XLA output is not stable
    across releases), the ModelSpec hash, the compute dtype and the hash
    of the program's source.
    """
    import jax

    fp: Dict[str, Any] = {
        "format": _FORMAT_VERSION,
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "n_devices": jax.device_count(),
        "source": source_fingerprint(),
    }
    try:
        import jaxlib
        fp["jaxlib"] = jaxlib.__version__
    except Exception:  # pragma: no cover - jaxlib always ships with jax
        fp["jaxlib"] = "unknown"
    if mesh is not None:
        fp["mesh"] = [[str(name), int(size)]
                      for name, size in mesh.shape.items()]
    if spec is not None:
        fp["spec"] = model_fingerprint(spec)
    if compute_dtype is not None:
        import numpy as np
        try:
            fp["compute_dtype"] = np.dtype(compute_dtype).name
        except TypeError:
            fp["compute_dtype"] = repr(compute_dtype)
    if extra:
        fp.update(extra)
    return fp


@dataclasses.dataclass
class StoreStats:
    loads: int = 0           # successful warm loads
    saves: int = 0           # entries written
    stale_skips: int = 0     # fingerprint mismatch -> cold compile
    corrupt_skips: int = 0   # bad sha / unreadable blob -> cold compile
    load_errors: int = 0     # deserialize raised -> cold compile
    save_errors: int = 0     # artifact not serializable / IO error
    gc_removed: int = 0      # entries evicted by gc()
    gc_removed_bytes: int = 0

    def as_dict(self) -> Dict[str, int]:
        return dataclasses.asdict(self)


class CacheStore:
    """Persistent bucket-key -> serialized-executable store.

    Implements the two-method protocol ``CompileCache`` expects from its
    ``store=`` backend:

    * ``load(key) -> artifact | None`` — ``None`` means "cold compile"
      (missing, stale fingerprint, corrupted, or failed to deserialize);
    * ``save(key, artifact, compile_seconds=...)`` — best-effort; an
      artifact that is not a serializable ``jax.stages.Compiled`` (or a
      full disk) degrades to a no-op, never an exception.
    """

    def __init__(self, directory: str | Path,
                 fingerprint: Optional[Dict[str, Any]] = None, *,
                 log: Optional[Callable[[str], None]] = None):
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        # canonicalize to JSON-native form ONCE: load() compares the
        # in-memory fingerprint against one that round-tripped through the
        # sidecar, so tuples must already be lists and exotic values
        # strings — otherwise every entry reads as permanently stale
        self.fingerprint: Dict[str, Any] = json.loads(
            json.dumps(dict(fingerprint or {}), sort_keys=True,
                       default=str))
        self.fp_hash = hashlib.sha256(
            json.dumps(self.fingerprint, sort_keys=True).encode()
        ).hexdigest()[:12]
        self.log = log
        self.stats = StoreStats()

    # ------------------------------------------------------------------
    @staticmethod
    def _key_hash(key: Hashable) -> str:
        return hashlib.sha256(repr(key).encode()).hexdigest()[:24]

    def _paths(self, key: Hashable) -> tuple[Path, Path]:
        h = f"{self._key_hash(key)}__{self.fp_hash}"
        return self.dir / f"{h}.bin", self.dir / f"{h}.meta.json"

    def _say(self, msg: str) -> None:
        if self.log:
            self.log(msg)

    # ------------------------------------------------------------------
    def load(self, key: Hashable) -> Optional[Any]:
        """Deserialize the entry for ``key``; None on any reason to cold
        compile (missing / stale fingerprint / corrupt / load failure)."""
        bin_path, meta_path = self._paths(key)
        if not (bin_path.exists() and meta_path.exists()):
            # entry persisted under a DIFFERENT topology/config only:
            # observable as a stale skip (the elastic shrink sees phase
            # 1's buckets but must not load them)
            if any(self.dir.glob(f"{self._key_hash(key)}__*.bin")):
                self.stats.stale_skips += 1
                self._say(f"[cache-store] stale fingerprint for {key} "
                          f"(topology/config changed) — cold compile")
            return None
        try:
            meta = json.loads(meta_path.read_text())
        except (OSError, json.JSONDecodeError):
            self.stats.corrupt_skips += 1
            self._say(f"[cache-store] unreadable sidecar for {key} — "
                      f"cold compile")
            return None
        if meta.get("fingerprint") != self.fingerprint:
            self.stats.stale_skips += 1
            self._say(f"[cache-store] stale fingerprint for {key} "
                      f"(topology/config changed) — cold compile")
            return None
        try:
            blob = bin_path.read_bytes()
        except OSError:
            self.stats.corrupt_skips += 1
            return None
        if (meta.get("payload_sha") !=
                hashlib.sha256(blob).hexdigest()):
            self.stats.corrupt_skips += 1
            self._say(f"[cache-store] corrupted payload for {key} — "
                      f"cold compile")
            return None
        try:
            from jax.experimental import serialize_executable
            payload, in_tree, out_tree = pickle.loads(blob)
            compiled = serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree)
        except Exception as e:  # noqa: BLE001 - any failure => cold compile
            self.stats.load_errors += 1
            self._say(f"[cache-store] deserialize failed for {key}: "
                      f"{e!r} — cold compile")
            return None
        self.stats.loads += 1
        try:
            # recency marker for gc(): least-recently-LOADED entries are
            # evicted first, not just least-recently-written ones
            os.utime(bin_path)
        except OSError:  # pragma: no cover - touch is best-effort
            pass
        return compiled

    # ------------------------------------------------------------------
    def save(self, key: Hashable, compiled: Any, *,
             compile_seconds: float = 0.0) -> bool:
        """Serialize ``compiled`` (a ``jax.stages.Compiled``) under
        ``key``. Best-effort: returns False instead of raising when the
        artifact cannot be serialized."""
        if compiled is None:
            return False
        try:
            from jax.experimental import serialize_executable
            payload, in_tree, out_tree = serialize_executable.serialize(
                compiled)
            blob = pickle.dumps((payload, in_tree, out_tree))
        except Exception as e:  # noqa: BLE001 - jit fns, plain values, ...
            self.stats.save_errors += 1
            self._say(f"[cache-store] cannot serialize {key}: {e!r}")
            return False
        meta = {
            "fingerprint": self.fingerprint,
            "key": repr(key),
            "compile_seconds": round(float(compile_seconds), 3),
            "payload_sha": hashlib.sha256(blob).hexdigest(),
            "payload_bytes": len(blob),
            "created": time.time(),
        }
        bin_path, meta_path = self._paths(key)
        try:
            # sidecar FIRST: a crash in between leaves an orphan meta
            # (load() sees no .bin => plain miss), never an orphan .bin
            # that would count as a misleading stale skip
            self._atomic_write(meta_path,
                               json.dumps(meta, indent=1).encode())
            self._atomic_write(bin_path, blob)
        except Exception as e:  # noqa: BLE001 - save is best-effort
            self.stats.save_errors += 1
            self._say(f"[cache-store] write failed for {key}: {e!r}")
            return False
        self.stats.saves += 1
        self._say(f"[cache-store] saved bucket {key} "
                  f"({len(blob) / 1e6:.2f} MB)")
        return True

    @staticmethod
    def _atomic_write(path: Path, data: bytes) -> None:
        tmp = path.with_name(path.name + f".tmp{os.getpid()}")
        tmp.write_bytes(data)
        os.replace(tmp, path)

    # ------------------------------------------------------------------
    def gc(self, max_age_s: Optional[float] = None,
           max_bytes: Optional[int] = None) -> Dict[str, Any]:
        """Evict stale bulk from the store: entries not loaded (or written)
        within ``max_age_s`` are removed, then — oldest first — entries are
        removed until the payload total fits ``max_bytes``. ``load()``
        touches an entry's mtime, so "oldest" means least-recently-USED,
        not least-recently-written. ``None`` disables the corresponding
        limit (gc(None, None) is a no-op). Train/serve call this at
        startup; removal order is fingerprint-blind — a topology nobody
        runs anymore ages out like any other entry.

        Deletion removes the ``.bin`` before its sidecar: a crash in
        between leaves an orphan sidecar, which ``load()`` treats as a
        plain miss (never a false stale/corrupt signal)."""
        ents = []
        for bin_path in self.dir.glob("*.bin"):
            try:
                st = bin_path.stat()
            except OSError:
                continue
            ents.append((st.st_mtime, st.st_size, bin_path))
        ents.sort()
        now = time.time()
        removed = removed_bytes = 0
        kept: List[tuple] = []
        for mtime, size, p in ents:
            if max_age_s is not None and now - mtime > max_age_s:
                if self._remove_entry(p):
                    removed += 1
                    removed_bytes += size
            else:
                kept.append((mtime, size, p))
        if max_bytes is not None:
            total = sum(sz for _, sz, _ in kept)
            for mtime, size, p in kept:
                if total <= max_bytes:
                    break
                if self._remove_entry(p):
                    removed += 1
                    removed_bytes += size
                    total -= size
        self.stats.gc_removed += removed
        self.stats.gc_removed_bytes += removed_bytes
        out = {"removed": removed, "removed_bytes": removed_bytes,
               "remaining_bytes": self.size_bytes()}
        if removed:
            self._say(f"[cache-store] gc removed {removed} entries "
                      f"({removed_bytes / 1e6:.2f} MB), "
                      f"{out['remaining_bytes'] / 1e6:.2f} MB remain")
        return out

    def _remove_entry(self, bin_path: Path) -> bool:
        meta_path = bin_path.with_name(
            bin_path.name[:-len(".bin")] + ".meta.json")
        try:
            bin_path.unlink()
        except OSError:
            return False
        try:
            meta_path.unlink()
        except OSError:  # orphan sidecar == plain miss; harmless
            pass
        return True

    # ------------------------------------------------------------------
    def entries(self) -> List[Dict[str, Any]]:
        """Sidecar metadata of every well-formed entry (any fingerprint)."""
        out = []
        for meta_path in sorted(self.dir.glob("*.meta.json")):
            bin_path = meta_path.with_name(
                meta_path.name[:-len(".meta.json")] + ".bin")
            if not bin_path.exists():
                continue
            try:
                out.append(json.loads(meta_path.read_text()))
            except (OSError, json.JSONDecodeError):
                continue
        return out

    def size_bytes(self) -> int:
        return sum(p.stat().st_size for p in self.dir.glob("*.bin"))

    def audit(self) -> List[Dict[str, Any]]:
        """Offline integrity/metadata audit of every on-disk entry — the
        substrate ``python -m repro.lint --cache-dir`` reports over.

        Unlike :meth:`load` this is fingerprint-blind and jax-free: it
        never deserializes an executable, only cross-checks each sidecar
        against its payload. Per entry: the sidecar's recorded
        ``payload_sha``/``payload_bytes`` against the actual ``.bin``
        bytes, required metadata fields, and whether the entry matches
        THIS store's fingerprint (a stale entry is not a finding — gc
        handles age — but a corrupt or truncated one is)."""
        required = ("fingerprint", "key", "payload_sha", "payload_bytes")
        out: List[Dict[str, Any]] = []
        for meta_path in sorted(self.dir.glob("*.meta.json")):
            bin_path = meta_path.with_name(
                meta_path.name[:-len(".meta.json")] + ".bin")
            row: Dict[str, Any] = {"entry": meta_path.name, "problems": []}
            try:
                meta = json.loads(meta_path.read_text())
            except (OSError, json.JSONDecodeError) as e:
                row["problems"].append(f"unreadable sidecar: {e}")
                out.append(row)
                continue
            row["key"] = meta.get("key", "?")
            for f in required:
                if f not in meta:
                    row["problems"].append(f"sidecar missing field {f!r}")
            if not bin_path.exists():
                row["problems"].append("orphan sidecar (no payload .bin)")
                out.append(row)
                continue
            try:
                blob = bin_path.read_bytes()
            except OSError as e:
                row["problems"].append(f"unreadable payload: {e}")
                out.append(row)
                continue
            if "payload_bytes" in meta and len(blob) != meta["payload_bytes"]:
                row["problems"].append(
                    f"payload is {len(blob)} bytes, sidecar recorded "
                    f"{meta['payload_bytes']} (truncated write?)")
            if "payload_sha" in meta:
                sha = hashlib.sha256(blob).hexdigest()
                if sha != meta["payload_sha"]:
                    row["problems"].append(
                        "payload sha256 mismatch (corrupt entry; load() "
                        "would skip it)")
            row["stale"] = meta.get("fingerprint") != self.fingerprint
            out.append(row)
        return out

    def report(self) -> Dict[str, Any]:
        """The block the train log / benchmarks JSON surface per store."""
        entries = self.entries()
        fresh = sum(1 for e in entries
                    if e.get("fingerprint") == self.fingerprint)
        return {
            "dir": str(self.dir),
            "entries": len(entries),
            "entries_current_fingerprint": fresh,
            "size_bytes": self.size_bytes(),
            **self.stats.as_dict(),
        }
