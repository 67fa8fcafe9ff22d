"""StepTimeline: the low-overhead collection half of online re-planning.

One recorder per driver (train / serve). Events go into a bounded ring
buffer (``collections.deque``) and, when ``--telemetry-dir`` is set, are
mirrored line-by-line into an append-only JSONL spill the analysis tooling
tails (``launch/analysis.telemetry_report``). Always-on accounting is cheap
(a dict update + an EMA multiply per step); the expensive per-stage probe —
``jax.block_until_ready`` brackets around the step — is opt-in and sampled
every ``probe_every`` steps by the caller.

Event kinds (the schema documented in runtime/README.md):

* ``step``         — one training step: bucket, wall seconds, tokens, loss,
                     optional per-stage seconds (probe mode only).
* ``probe``        — per-stage breakdown sampled under block_until_ready.
* ``compile``      — compile-cache event (cold miss / warm load / hit rates).
* ``lint``         — program-auditor findings attributed to a bucket.
* ``engine``       — serve-engine sample: TTFT/TPOT percentiles, occupancy.
* ``calibration``  — a new CostCalibration version was adopted.
* ``replan``       — re-plan trigger / decision / swap (replan.py).

``span`` is the program's host span on the profiler's clock: a
``jax.profiler.TraceAnnotation`` (``StepTraceAnnotation`` with a
``step_num``) that also times its body with ``time.perf_counter``, so a
timeline event and the profiler's span report one reading.
"""

from __future__ import annotations

import json
import time
from collections import defaultdict, deque
from pathlib import Path
from typing import Any, Dict, List, Optional

__all__ = ["StepEvent", "StepTimeline", "span"]

EMA_DECAY = 0.3  # weight of the newest sample in the per-bucket step EMA


class span:
    """``with span("repro.<name>", **args) as s:`` — a profiler span named
    ``name`` around the body; ``s.elapsed()`` inside and ``s.seconds``
    after it are ``perf_counter`` readings from the span's own start.

    ``step_num`` in ``args`` makes it a ``StepTraceAnnotation`` (a step
    marker for the profiler's step view). JAX is imported on first
    entry, so this module stays importable without it; without JAX the
    span only times. Names start with ``repro.``: the benchmark's own
    spans (``window``, ``plan``, ...) keep names of their own.
    """

    __slots__ = ("name", "args", "t0", "seconds", "_ann")

    def __init__(self, name: str, **args: Any) -> None:
        if not name.startswith("repro."):
            raise ValueError(f"program span names start with 'repro.', "
                             f"got {name!r}")
        self.name, self.args = name, args
        self.seconds = 0.0

    def __enter__(self) -> "span":
        self._ann = _annotation(self.name, self.args)
        if self._ann is not None:
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def __exit__(self, *exc) -> None:
        self.seconds = self.elapsed()
        if self._ann is not None:
            self._ann.__exit__(*exc)


def _annotation(name: str, args: Dict[str, Any]):
    try:
        from jax import profiler
    except ImportError:
        return None
    if "step_num" in args:
        return profiler.StepTraceAnnotation(name, **args)
    return profiler.TraceAnnotation(name, **args)


class StepEvent(dict):
    """A timeline event is a plain dict (JSON-ready); attribute sugar only."""

    @property
    def kind(self) -> str:
        return self.get("kind", "?")


class StepTimeline:
    """Ring buffer + JSONL spill + always-on per-bucket EMA counters."""

    def __init__(self, capacity: int = 1024,
                 spill_dir: Optional[str] = None,
                 name: str = "train", clock=time.time) -> None:
        self.name = name
        self._clock = clock
        self._events: deque = deque(maxlen=max(4, capacity))
        self._by_kind: Dict[str, int] = defaultdict(int)
        # per-bucket always-on counters: EMA step seconds, count, last value
        self._buckets: Dict[str, Dict[str, float]] = {}
        self._spill_path: Optional[Path] = None
        self._spill = None
        self.dropped_spill_writes = 0
        if spill_dir:
            d = Path(spill_dir)
            d.mkdir(parents=True, exist_ok=True)
            self._spill_path = d / f"timeline-{name}.jsonl"
            self._spill = open(self._spill_path, "a", buffering=1)

    # -- recording ---------------------------------------------------------

    def record(self, kind: str, step: int = -1, **data: Any) -> StepEvent:
        ev = StepEvent(kind=kind, step=step, t=round(self._clock(), 6),
                       **data)
        self._events.append(ev)
        self._by_kind[kind] += 1
        if self._spill is not None:
            try:
                self._spill.write(json.dumps(ev, default=str) + "\n")
            except (OSError, ValueError):
                # telemetry must never take the training loop down
                self.dropped_spill_writes += 1
        return ev

    def record_step(self, step: int, bucket: Any, wall_s: float, *,
                    tokens: float = 0.0, loss: Optional[float] = None,
                    per_stage_s: Optional[List[float]] = None,
                    probed: bool = False, **extra: Any) -> StepEvent:
        """The always-on per-step sample. ``bucket`` is any hashable bucket
        identity (a ``BucketKey`` or its string form); ``per_stage_s`` is
        only present on probed steps."""
        b = str(bucket)
        st = self._buckets.setdefault(
            b, {"ema_s": 0.0, "n": 0, "last_s": 0.0})
        st["n"] += 1
        st["last_s"] = wall_s
        st["ema_s"] = (wall_s if st["n"] == 1 else
                       EMA_DECAY * wall_s + (1 - EMA_DECAY) * st["ema_s"])
        data: Dict[str, Any] = {"bucket": b, "wall_s": round(wall_s, 6),
                                "tokens": tokens, "probed": probed}
        if loss is not None:
            data["loss"] = loss
        if per_stage_s is not None:
            data["per_stage_s"] = [round(float(x), 6) for x in per_stage_s]
        data.update(extra)
        if probed and per_stage_s is not None:
            self.record("probe", step, bucket=b,
                        per_stage_s=data["per_stage_s"])
        return self.record("step", step, **data)

    # -- reading -----------------------------------------------------------

    def ema(self, bucket: Any) -> float:
        """Smoothed step seconds for a bucket (0.0 if never seen)."""
        st = self._buckets.get(str(bucket))
        return float(st["ema_s"]) if st else 0.0

    def events(self, kind: Optional[str] = None) -> List[StepEvent]:
        if kind is None:
            return list(self._events)
        return [e for e in self._events if e.kind == kind]

    def snapshot(self) -> Dict[str, Any]:
        """The ``--stats-json``-ready summary (never the full ring)."""
        return {
            "name": self.name,
            "events": sum(self._by_kind.values()),
            "by_kind": dict(self._by_kind),
            "per_bucket": {
                b: {"ema_s": round(st["ema_s"], 6), "n": int(st["n"]),
                    "last_s": round(st["last_s"], 6)}
                for b, st in self._buckets.items()},
            "spill": str(self._spill_path) if self._spill_path else None,
            "dropped_spill_writes": self.dropped_spill_writes,
        }

    def close(self) -> None:
        if self._spill is not None:
            try:
                self._spill.close()
            finally:
                self._spill = None

    def __enter__(self) -> "StepTimeline":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
