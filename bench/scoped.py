"""Device time by the program's scopes in one benchmark cell.

    python bench/scoped.py --workload <name> --seed <n> [--seconds <s>] \
        [--trace-out <file.json.gz>]

Set-up as ``bench/run.py`` (without the reference check), then an
untraced window of ``--seconds`` and one traced pass over the replay set.
Prints one JSON line: the step wall of both, the trace's busy and window
time, the scope reduction (``bench/scopes.py``) and the readings of the
metrics that read it, how the step executables were obtained (store
loads, compiles) and the ``repro.*`` host spans the trace holds. With
``--trace-out`` the compact trace and the scope maps are kept as JSON.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import devtrace  # noqa: E402
import harness  # noqa: E402
import scopes  # noqa: E402
from metrics import reader  # noqa: E402

METRICS = ("ce_head_ms", "attention_ms", "mlp_ms", "recompute_share")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace-out")
    args = ap.parse_args()
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    harness.configure_jax()
    run = harness.Run(harness.resolve(args.workload), args.seed, log=log,
                      t_start=T_START)
    try:
        run.prepare()
    except harness.NoChip as e:
        log(f"FAIL: {e}")
        return 2
    maps = {}
    for _, compiled in run.steps.values():
        maps.update(scopes.scope_map(compiled))
    plain = run.window(args.seconds)
    with tempfile.TemporaryDirectory() as tdir:
        traced = run.window(0, trace_dir=tdir)
        trace = scopes.load(tdir)
    n = len(traced["steps"])
    red = scopes.reduce(trace, maps, n)
    spans = {}
    for name, _, d in trace["spans"]:
        c, t = spans.get(name, (0, 0.0))
        spans[name] = (c + 1, t + d / 1e9)
    base = devtrace.reduce(trace, n)
    stats = run.path.cache.stats
    out = {
        "step_s": {"untraced": plain["elapsed"] / len(plain["steps"]),
                   "traced": traced["elapsed"] / n},
        "steps": {"untraced": len(plain["steps"]), "traced": n},
        "busy_s": base["busy_s"], "window_s": base["window_s"],
        "scopes": red,
        "metrics": {m: reader(m)({"scopes": red}) for m in METRICS},
        "executables": {"store_loads": stats.warm_hits,
                        "compiles": stats.misses,
                        "ops_with_op_name": {k: len(v)
                                             for k, v in maps.items()}},
        "trace_modules": sorted({scopes.module_name(e[0])
                                 for evs in trace["modules"].values()
                                 for e in evs}),
        "spans": spans,
    }
    if args.trace_out:
        with gzip.open(args.trace_out, "wt") as f:
            json.dump({"trace": trace, "maps": maps}, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
