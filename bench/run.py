"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration (``bench/configs``),
a traffic mix (``bench/traffic``) and the chips it needs. With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of one
pass over the replay set. The last line of standard output is the result;
the numbers that decide ``correct`` are the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 2.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    try:
        cell = harness.resolve(args.workload)
        out = harness.execute(cell, args.seed, args.seconds,
                              bool(args.trace), log=log, t_start=T_START)
    except harness.NoChip as e:
        log(f"FAIL: {e}")
        return 2
    for name, c in out["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
