"""One reader per per-layer metric, found by the metric's name.

Each module ``<name>.py`` defines ``read(run) -> float | None``. ``run``
is the dict the harness builds from the traced window:

- ``steps``: one dict per step of the window, with ``real`` (target-
  bearing tokens), ``slots`` (chunks x capacity of its bucket), ``bucket``
  and ``solve_ms`` (the benchmark's span around ``plan_batch``);
- ``peaks`` (compiled peak bytes per bucket) and ``bytes_limit``;
- ``trace``: the reduction of ``bench/devtrace.py``, or None;
- ``flops`` (model FLOPs of the window's steps, ``bench/flops.py``),
  ``chips`` and ``peak_flops`` (``bench/peaks.py``).

A reader that finds nothing to read returns None, and the metric is left
out of the result line.
"""

import importlib


def reader(name: str):
    return importlib.import_module(f"metrics.{name}").read
