"""One reader per per-layer metric, found by the metric's name.

Each module ``<name>.py`` defines ``read(run) -> float | None``. ``run``
is the dict the harness builds from the traced window:

- ``steps``: one dict per step of the window, with ``real`` (target-
  bearing tokens), ``docs`` (its documents' lengths), ``slots`` (chunks x
  capacity of its bucket), ``bucket``, ``solve_ms`` (the benchmark's
  span around ``plan_batch``) and ``counters``: every metric the
  compiled step returns but its loss (today ``tokens``, ``grad_norm``,
  ``lr``), read from the device once after the step, as NumPy values; a
  family's step that counts more (routed pairs, say) hands it to its
  readers here;
- ``peaks`` (compiled peak bytes per bucket) and ``bytes_limit``;
- ``trace``: the reduction of ``bench/devtrace.py``, or None;
- ``scopes``: the reduction of ``bench/scopes.py`` (device time a step
  by the program's named scopes), or None;
- ``config``: the cell's configuration (``bench/configs``);
- ``flops`` (model FLOPs of the window's steps, ``bench/flops.py``),
  ``chips``, ``peak_flops`` and ``peak_bytes_per_s`` (``bench/peaks.py``).

A reader that reads the device time of one of the program's named scopes
declares it, ``SCOPE = "<name>"``: ``bench/scopes.py`` counts a scope that
some reader declares, so a new scope needs a new reader and no edit.

A reader that finds nothing to read returns None, and the metric is left
out of the result line.
"""

import importlib
import pkgutil


def reader(name: str):
    return importlib.import_module(f"{__name__}.{name}").read


def declared_scopes() -> tuple:
    """The scopes that the readers of this package declare, sorted."""
    names = (m.name for m in pkgutil.iter_modules(__path__))
    return tuple(sorted({s for s in (
        getattr(importlib.import_module(f"{__name__}.{n}"), "SCOPE", None)
        for n in names) if s}))
