"""Device time by the program's named scopes.

The step program names its layers with ``jax.named_scope``: ``embed``,
``attention``, ``mlp``, ``ce_head``, ``optimizer``, ``handoff``,
``sp_collective`` and, outermost, ``stage_tick``. The names reach the
compiled module as each instruction's ``op_name`` metadata, e.g.
``jit(f)/transpose(jvp(stage_tick))/.../rematted_computation/attention/
dot_general``: the innermost of the eight names is the op's layer,
``transpose(`` marks the backward and ``rematted_computation`` the
recomputation. The profiler names device ops by instruction only, so
``scope_map`` reads the compiled module's text and ``reduce`` joins the
two.

``load`` reads the ``.xplane.pb`` into a compact dict, a superset of
``devtrace.load``'s that ``devtrace.reduce`` reads as it reads that one:
besides, the ``XLA Modules`` intervals and the program's ``repro.*``
host spans.
"""

from __future__ import annotations

import bisect
import glob
import re
from typing import Dict, Optional

import devtrace

SCOPES = ("embed", "attention", "mlp", "ce_head", "optimizer", "handoff",
          "sp_collective", "stage_tick")
REMAT = "rematted_computation"
MODULES_LINE = "XLA Modules"
_MODULE = re.compile(r"^HloModule ([^\s,]+)")
_INSTR = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?\bop_name="((?:[^"\\]|\\.)*)"')
_BASE = re.compile(r"^(?:[\w\-]+\()*([^()]*)\)*$")


def scope_map(compiled) -> Dict[str, Dict[str, str]]:
    """``{module: {instruction: op_name}}`` from ``compiled.as_text()``
    (instructions without ``op_name`` are left out)."""
    text = compiled.as_text()
    m = _MODULE.match(text)
    if not m:
        raise ValueError("not an HLO module's text")
    ops = {}
    for line in text.splitlines():
        hit = _INSTR.match(line)
        if hit:
            ops[hit.group(1)] = hit.group(2)
    return {m.group(1): ops}


def module_name(event: str) -> str:
    """``jit_step(1234)`` -> ``jit_step``: a module event's name without
    the run's program id."""
    return event.split("(", 1)[0].strip()


def layer_of(op_name: str) -> str:
    """The innermost of ``SCOPES`` in an ``op_name`` path, or
    ``unscoped``; transform wrappers (``jvp(...)``) are looked through."""
    for part in reversed(op_name.split("/")):
        base = _BASE.match(part)
        if base and base.group(1) in SCOPES:
            return base.group(1)
    return "unscoped"


def load(trace_dir: str) -> Dict:
    """``devtrace.load``'s ``devices`` and ``host`` (the benchmark's
    spans) from the newest xplane, with ``modules`` (``{dev: [[module,
    start_ns, dur_ns], ...]}``) and ``spans`` (the program's ``repro.*``
    host spans, ``[[name, start_ns, dur_ns], ...]``)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out: Dict = {"devices": {}, "modules": {}, "host": [], "spans": []}
    for plane in ProfileData.from_file(paths[-1]).planes:
        m = devtrace.DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                key = {devtrace.OPS_LINE: "devices",
                       MODULES_LINE: "modules"}.get(line.name)
                if key:
                    out[key][m.group(1)] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    key = ("host" if e.name in devtrace.HOST_SPANS else
                           "spans" if e.name.startswith("repro.") else None)
                    if key:
                        out[key].append([e.name, float(e.start_ns),
                                         float(e.duration_ns)])
    return out


def reduce(trace: Dict, maps: Dict[str, Dict[str, str]],
           n_steps: int) -> Dict:
    """Leaf-op device time of the ``window`` span by layer, per step and
    averaged over the chips, in ms.

    Each leaf op (not one of ``devtrace.CONTAINERS``) is clipped to the
    window and looked up in the map of the module whose ``XLA Modules``
    interval holds its clipped start (outside every interval: the only
    map, if there is one); an op with no ``op_name`` there, or with none
    of ``SCOPES`` in it, is ``unscoped``. Returns ``leaf_ms``, ``layers_ms`` (the eight scopes
    and ``unscoped``, summing to ``leaf_ms``), ``backward_ms`` (the part
    of each under ``transpose(``), ``remat_ms`` (under
    ``rematted_computation``), ``unmapped_ms`` (ops of a module with no
    map) and ``scoped`` (whether any op fell under a scope: a program
    built without scopes reads False).
    """
    wins = [(s, s + d) for name, s, d in trace["host"] if name == "window"]
    if len(wins) != 1:
        raise ValueError(f"expected one 'window' span, found {len(wins)}")
    w0, w1 = wins[0]
    devices = trace["devices"]
    if not devices:
        raise ValueError("the trace holds no device operations")
    layers = {k: 0.0 for k in SCOPES + ("unscoped",)}
    backward = dict(layers)
    remat = unmapped = 0.0
    only = next(iter(maps.values())) if len(maps) == 1 else None
    for dev, events in devices.items():
        mods = sorted(trace.get("modules", {}).get(dev, []),
                      key=lambda e: e[1])
        starts = [s for _, s, _ in mods]
        for op, s, d in events:
            s0 = max(s, w0)
            t = min(s + d, w1) - s0
            name = devtrace.op_name(op)
            if t <= 0 or name.split(".")[0] in devtrace.CONTAINERS:
                continue
            ops = _module_map(mods, starts, s0, maps, only)
            if ops is None:
                unmapped += t
                ops = {}
            path = ops.get(name, "")
            layer = layer_of(path)
            layers[layer] += t
            if "transpose(" in path:
                backward[layer] += t
            if REMAT in path.split("/"):
                remat += t
    ms = 1e-6 / len(devices) / max(n_steps, 1)
    return {
        "scoped": any(v for k, v in layers.items() if k != "unscoped"),
        "leaf_ms": sum(layers.values()) * ms,
        "layers_ms": {k: v * ms for k, v in layers.items()},
        "backward_ms": {k: v * ms for k, v in backward.items()},
        "remat_ms": remat * ms,
        "unmapped_ms": unmapped * ms,
    }


def _module_map(mods, starts, s, maps, only) -> Optional[Dict[str, str]]:
    """The map of the module interval that holds time ``s`` (None for a
    module with no map); outside every interval, the only map."""
    i = bisect.bisect_right(starts, s) - 1
    if i >= 0 and s < mods[i][1] + mods[i][2]:
        return maps.get(module_name(mods[i][0]))
    return only
