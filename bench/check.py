"""The comparison that decides ``correct``.

The program and the plain reference (``bench/configs/<family>.py``) each
train the same first steps from the same seed. Compared:

- ``loss_gap``: the largest gap between the two losses over the steps.
  Both compute with bf16 copies of float32 master weights, the
  configuration's precision, so the later steps compare as the first;
- ``grad_gap``: the first clipped gradient, as the optimizer received it,
  leaf by leaf: the gap between the two leaf norms over the larger of the
  reference's leaf norm and its median leaf norm, worst leaf;
- ``delta_gap``: as ``grad_gap``, for the master weights' change over the
  steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (they move by round-off alone);
- ``param_gap``: the same for the change of the parameters the step
  returns, which the next step computes with, against the reference's
  compute copies;
- ``tokens_gap``: the largest gap, over every step the program ran,
  between the target-bearing tokens it counted and the benchmark's count.

Each number has the limit of the cell's ``bench/limits/<cell>.json``.
"""

from __future__ import annotations

import json
import statistics
from pathlib import Path
from typing import Dict

HERE = Path(__file__).resolve().parent
NUMBERS = ("loss_gap", "grad_gap", "delta_gap", "param_gap", "tokens_gap")
NEGLIGIBLE = 1e-3


def load_limits(cell: str, directory: Path = HERE / "limits") -> Dict:
    lim = json.loads((directory / f"{cell}.json").read_text())
    missing = [k for k in NUMBERS if k not in lim]
    if missing:
        raise ValueError(f"limits of {cell!r} lack {missing}")
    return lim


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              keep=lambda k: True) -> Dict[str, float]:
    """Per leaf: the gap between the program's norm and the reference's,
    over the larger of the reference's norm and its median leaf's."""
    if set(prog) != set(ref):
        return {"<leaves differ>": float("inf")}
    names = [k for k in ref if keep(k)]
    med = statistics.median(ref[k] for k in names)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in names}


def numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    n = len(ref["losses"])
    losses = prog["losses"][:n]
    loss_gap = (max(abs(a - b) for a, b in zip(losses, ref["losses"]))
                if len(losses) == n else float("inf"))
    med_g = statistics.median(ref["grad"].values())
    moving = {k for k, g in ref["grad"].items() if g >= NEGLIGIBLE * med_g}
    return {
        "loss_gap": loss_gap,
        "grad_gap": max(leaf_gaps(prog["grad"], ref["grad"]).values()),
        "delta_gap": max(leaf_gaps(prog["delta"], ref["delta"],
                                   lambda k: k in moving).values()),
        "param_gap": max(leaf_gaps(prog["copy_delta"], ref["copy_delta"],
                                   lambda k: k in moving).values()),
        "tokens_gap": float(prog["tokens_gap"]),
    }


def verdict(nums: Dict[str, float], limits: Dict) -> Dict[str, Dict]:
    return {k: {"value": nums[k], "limit": float(limits[k]),
                "ok": bool(nums[k] <= float(limits[k]))} for k in NUMBERS}
