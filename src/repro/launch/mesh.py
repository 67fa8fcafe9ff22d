"""Meshes, backend facts and the persistent compile cache.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state; the dry-run sets its
placeholder-device XLA flag before any jax import (launch/dryrun.py).

Axis semantics (DESIGN.md §2.2):
  pod   — data parallel across pods (gradient all-reduce, optionally int8)
  data  — pipeline stages d_p (stage-stacked params + ppermute)
  model — SP/FSDP/EP d_s (ulysses / allgather-KV, ZeRO-3, expert parallel)
"""

from __future__ import annotations

import math
import os
from pathlib import Path
from typing import Optional, Sequence, Tuple

# JAX's persistent compilation cache lives here unless the environment
# names another directory. The path is part of every cache key, so it is
# fixed: a per-run (temporary, pid- or time-stamped) directory never hits.
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_COMPILE_CACHE = CHECKOUT_ROOT / ".jax_cache"


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(DEFAULT_COMPILE_CACHE))


def enable_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    unless a directory is already configured (JAX itself reads
    ``JAX_COMPILATION_CACHE_DIR``). Returns the directory in use.

    The CPU backend is left alone unless the environment names a
    directory: its programs compile in seconds, and reloading them logs
    host-feature mismatch errors."""
    import jax
    if (not jax.config.jax_compilation_cache_dir
            and jax.default_backend() != "cpu"):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    return jax.config.jax_compilation_cache_dir


def latency_hiding_active() -> bool:
    """True when the backend overlaps collectives with compute.

    The TPU compiler schedules async collectives by default; the CPU
    backend runs them blocking. Used by the program auditor: blocking
    collectives are only a hazard when the run claims to overlap them.
    """
    import jax
    return jax.default_backend() == "tpu"


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices: Optional[Sequence] = None):
    """The one mesh constructor: ``Auto`` axes, so shard_map's manual
    collectives and jit's sharding propagation see the classic semantics
    (``jax.make_mesh`` defaults to ``Explicit`` axes). ``devices``
    defaults to the first ``prod(shape)`` devices of the default backend."""
    import jax
    from jax.sharding import AxisType
    if devices is None:
        devices = jax.devices()[:math.prod(shape)]
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    import jax
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    have = jax.device_count()
    if have < need:
        raise ValueError(
            f"make_production_mesh(multi_pod={multi_pod}) needs {need} "
            f"devices for mesh {dict(zip(axes, shape))} but "
            f"jax.device_count() == {have}; use launch.mesh.make_mesh() "
            f"with a shape matching your slice, or (CPU dry-runs) raise "
            f"--xla_force_host_platform_device_count.")
    return make_mesh(shape, axes)
