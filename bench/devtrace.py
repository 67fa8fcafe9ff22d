"""Profiler trace -> per-device busy time, collective time, top device
operations and idle gaps labelled by the benchmark's host spans.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
compact dict (so the reduction can be tested on a small recorded trace
kept as JSON); ``reduce`` computes every number from that dict alone.
"""

from __future__ import annotations

import glob
import re
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU:\d+)$")
OPS_LINE = "XLA Ops"
HOST_SPANS = ("window", "plan", "materialize", "dispatch", "block")
COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "reduce-scatter", "all-to-all")
# ops that only hold others (a loop, a branch): their time is their
# children's, so they stay out of the top operations
CONTAINERS = ("while", "conditional", "call")


def op_name(event: str) -> str:
    """``%fusion.849 = bf16[...] fusion(...)`` -> ``fusion.849``."""
    return event.split(" = ", 1)[0].lstrip("%").strip()


def load(trace_dir: str) -> Dict:
    """``{"devices": {name: [[op, start_ns, dur_ns], ...]},
    "host": [[span, start_ns, dur_ns], ...]}`` from the newest xplane."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out: Dict = {"devices": {}, "host": []}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out["devices"][m.group(1)] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        out["host"].append([e.name, float(e.start_ns),
                                            float(e.duration_ns)])
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _label(gap: Tuple[float, float], host) -> str:
    """The host span (not the window itself) that covers most of a gap."""
    best, cover = "none", 0.0
    for name, s, d in host:
        if name == "window":
            continue
        c = min(gap[1], s + d) - max(gap[0], s)
        if c > cover:
            best, cover = name, c
    return best


def reduce(trace: Dict, n_steps: int) -> Dict:
    """Numbers of the traced window (the host ``window`` span)."""
    wins = [(s, s + d) for name, s, d in trace["host"] if name == "window"]
    if len(wins) != 1:
        raise ValueError(f"expected one 'window' span, found {len(wins)}")
    w0, w1 = wins[0]
    devices = trace["devices"]
    if not devices:
        raise ValueError("the trace holds no device operations")
    busy, coll, gaps = [], [], []
    ops: Dict[str, float] = {}
    for name, events in sorted(devices.items()):
        iv = []
        c = 0.0
        for op, s, d in events:
            s, e = max(s, w0), min(s + d, w1)
            if e <= s:
                continue
            iv.append((s, e))
            name_ = op_name(op)
            if not name_.split(".")[0] in CONTAINERS:
                ops[name_] = ops.get(name_, 0.0) + (e - s)
            if any(k in op.lower() for k in COLLECTIVES):
                c += e - s
        merged = _union(iv)
        busy.append(sum(e - s for s, e in merged))
        coll.append(c)
        edges = [w0] + [x for se in merged for x in se] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, _label((a, b), trace["host"])))
    n_dev = len(devices)
    window_ns = w1 - w0
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    gaps.sort(key=lambda g: -g[0])
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy) / n_dev / 1e9,
        "idle_share": 1.0 - sum(busy) / n_dev / window_ns,
        "collective_s_per_step": sum(coll) / n_dev / 1e9 / max(n_steps, 1),
        "device_ops": [[op, t / n_dev / 1e9] for op, t in top],
        "idle_gaps": [[label, g / 1e9] for g, label in gaps[:10]],
        "n_devices": n_dev,
    }
