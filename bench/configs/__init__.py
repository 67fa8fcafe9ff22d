"""Model configurations and the two modules each one names.

A configuration file (``<name>.json``) holds the published sizes as they
are run, and names two modules of this package:

- ``reference``: the plain reference of the family, which imports nothing
  of the program (``make_weights``, ``run_key``, ``pack``, ``train``, and
  ``leaf_norms(weights)``, which names every leaf the comparison checks);
- ``program``: the adapter that hands the family to the program, with
  ``arch(cfg)`` (the program's ``ArchConfig``), ``to_program(weights)``
  (the reference's canonical weights as the program's parameter tree),
  ``from_program(cfg, tree)`` (a tree of the program's parameter layout,
  after the program has stacked and padded it, back in the reference's
  layout: structure only, no value changed) and
  ``step_flops(cfg, doc_lengths)`` (model FLOPs of a step,
  ``bench/flops.py``).

The comparison names the program's leaves with the reference's own
``leaf_norms`` of ``from_program``'s tree, so a family whose layers differ
(a leading dense layer, sub-blocks that hold leaves of one name) lays its
trees out as it likes.

A new family joins the benchmark with new files alone: its configuration,
these two modules, a traffic mix and the cell's limits.
"""

from __future__ import annotations

import importlib
from types import ModuleType
from typing import Dict

PROGRAM_FUNCTIONS = ("arch", "to_program", "from_program", "step_flops")


def program(cfg: Dict) -> ModuleType:
    """The program adapter that ``cfg`` names, with every function of
    ``PROGRAM_FUNCTIONS``; raises ``ValueError`` naming what is missing."""
    name = cfg.get("program")
    if not name:
        raise ValueError("the configuration names no program adapter "
                         "(key 'program')")
    module = f"{__name__}.{name}"
    try:
        mod = importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise ValueError(f"no program adapter {name!r} "
                         f"(bench/configs/{name}.py)") from e
    missing = [f for f in PROGRAM_FUNCTIONS
               if not callable(getattr(mod, f, None))]
    if missing:
        raise ValueError(f"program adapter {name!r} lacks "
                         f"{', '.join(missing)}")
    return mod
