"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python bench/calibrate.py --workload <name> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--out chiprun_out/calib.jsonl]

For every seed of ``--seeds``: the cell's set-up as a run makes it (the
program's first steps through the window's own call and feed) against the
plain reference, as ``bench/check.py`` compares them. For every seed of
``--control-seeds``, the reference takes the program's place: computed in
fp8 (the control), and with each fault the cell can have planted in it
(half of the batch left out; compute copies never refreshed from the
master weights). A state left unchanged reads 1 on ``delta_gap`` and
``param_gap`` by construction and needs no run. One JSON line per
reading, with the worst leaf of each comparison; no measured window.
"""

import argparse
import json
import sys
import time
from pathlib import Path
from typing import Dict

sys.path.insert(0, str(Path(__file__).resolve().parent))

import check  # noqa: E402
import harness  # noqa: E402


def _look(prog, ref) -> Dict:
    """The worst leaf of each per-leaf comparison: its name, its gap, the
    program's norm and the reference's."""
    out = {}
    for name in ("grad", "delta", "copy_delta"):
        gaps = check.leaf_gaps(prog[name], ref[name])
        worst = max(gaps, key=gaps.get)
        out[name] = [worst, gaps[worst], prog[name].get(worst),
                     ref[name].get(worst)]
    return out


def readings(cell, seeds, control_seeds, emit, require_tpu=True):
    harness.configure_jax()
    path = None
    for seed in seeds:
        t0 = time.perf_counter()
        run = harness.Run(cell, seed, log=lambda m: None)
        run.prepare(require_tpu=require_tpu, path=path)
        path = run.path
        run.params = run.opt = None
        ref = run.reference()
        emit({"seed": seed, "kind": "program",
              "numbers": check.numbers(run.readings, ref),
              "losses": run.readings["losses"][:harness.CHECKED_STEPS],
              "ref_losses": ref["losses"],
              "worst": _look(run.readings, ref),
              "seconds": time.perf_counter() - t0})
        if seed in control_seeds:
            for kind, precision, variant in (
                    ("control", "fp8", None), ("half_batch", "f32", "half"),
                    ("unrefreshed", "f32", "unrefreshed")):
                other = run.reference(precision=precision, variant=variant)
                other["tokens_gap"] = 0
                emit({"seed": seed, "kind": kind,
                      "numbers": check.numbers(other, ref),
                      "losses": other["losses"],
                      "worst": _look(other, ref)})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    readings(harness.resolve(args.workload), seeds, control, emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
