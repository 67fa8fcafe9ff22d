"""Benchmark entry point: one function per paper table/figure + the roofline.

Prints ``name,us_per_call,derived`` CSV rows per the harness contract, plus
the full per-figure records.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig7,...]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json-out", default="",
                    help="also write the full per-figure records (incl. the "
                         "compile_cache stats block) to this JSON file — "
                         "CI uploads it as an artifact")
    ap.add_argument("--seed", type=int, default=0,
                    help="RNG seed stamped into the artifact meta (benches "
                         "that sample take it from here)")
    args = ap.parse_args()

    from . import paper_figures as pf

    figs = {
        "fig7_end_to_end": lambda: pf.fig7_end_to_end(
            batch=48 if args.quick else 96),
        "fig8_breakdown": pf.fig8_breakdown,
        "fig9_scalability": pf.fig9_scalability,
        "fig10_ablation": pf.fig10_ablation,
        "fig11_cost_model_accuracy": pf.fig11_cost_model_accuracy,
        "fig12_solver_scaling": pf.fig12_solver_scaling,
        "fig13_convergence": pf.fig13_convergence,
        "cache_bucket_reuse": lambda: pf.cache_bucket_reuse(
            steps=8 if args.quick else 24),
        "ckpt_policy": lambda: pf.ckpt_policy_compare(
            batch=32 if args.quick else 64),
        "pipeline_bubble": pf.pipeline_bubble,
        "sp_axis": lambda: pf.sp_axis(quick=args.quick),
        "serving_engine": lambda: __import__(
            "benchmarks.serving", fromlist=["serving_engine"]
        ).serving_engine(quick=args.quick),
        "paged_kv": lambda: __import__(
            "benchmarks.serving", fromlist=["paged_kv"]
        ).paged_kv(quick=args.quick),
        "replan": lambda: __import__(
            "benchmarks.replan", fromlist=["replan_drift"]
        ).replan_drift(quick=args.quick),
    }
    only = {x.strip() for x in args.only.split(",") if x.strip()}

    print("name,us_per_call,derived")
    all_rows = {}
    elapsed_s = {}
    failed = []
    for name, fn in figs.items():
        if only and not any(name.startswith(o) for o in only):
            continue
        t0 = time.perf_counter()
        try:
            rows = fn()
            status = "ok"
        except Exception as e:  # noqa: BLE001 — report, finish the rest
            traceback.print_exc()
            rows = [{"error": repr(e)}]
            status = "error"
            failed.append(name)
        dt = (time.perf_counter() - t0) * 1e6
        elapsed_s[name] = round(dt / 1e6, 3)
        derived = _derived(name, rows) if status == "ok" else status
        print(f"{name},{dt:.0f},{derived}", flush=True)
        all_rows[name] = rows

    # roofline summary (reads the dry-run artifacts if present)
    t0 = time.perf_counter()
    try:
        from .roofline import load_cells
        rows = load_cells()
        ok = [r for r in rows if r.status == "ok"]
        best = max((r.frac_of_roofline for r in ok), default=0)
        derived = (f"cells={len(rows)};ok={len(ok)};"
                   f"best_frac={best:.2f}")
    except Exception as e:  # noqa: BLE001
        derived = f"unavailable({e!r})"
    print(f"roofline,{(time.perf_counter() - t0) * 1e6:.0f},{derived}")

    # compile-cache statistics across every step built this process
    t0 = time.perf_counter()
    try:
        from repro.launch.analysis import (compile_cache_report,
                                           format_cache_report)
        cache_stats = compile_cache_report()
        derived = format_cache_report(cache_stats)
    except Exception as e:  # noqa: BLE001
        cache_stats = {"error": repr(e)}
        derived = f"unavailable({e!r})"
    print(f"compile_cache,{(time.perf_counter() - t0) * 1e6:.0f},{derived}")
    all_rows["compile_cache"] = [cache_stats]

    print("\n=== full records ===")
    for name, rows in all_rows.items():
        for r in rows:
            print(json.dumps({"bench": name, **r}))
    if args.json_out:
        # provenance: a BENCH artifact must say WHEN it was measured and
        # with WHICH seed, or two checked-in generations can't be compared
        all_rows["meta"] = [{
            "generated_at": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
            "generated_at_unix": round(time.time(), 3),
            "seed": args.seed,
            "quick": bool(args.quick),
            "elapsed_s": elapsed_s,
        }]
        with open(args.json_out, "w") as f:
            json.dump(all_rows, f, indent=1)
    if failed:
        print(f"error: benchmark rows failed: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    return 0


def _derived(name: str, rows) -> str:
    if name.startswith("fig7"):
        sp = [r["speedup_vs_flexsp"] for r in rows]
        return f"max_speedup_vs_flexsp={max(sp):.2f}x"
    if name.startswith("fig8"):
        return f"bubble={rows[0]['bubble_ratio']:.3f}"
    if name.startswith("fig9"):
        return f"rows={len(rows)}"
    if name.startswith("fig10"):
        rel = [r["relative"] for r in rows if isinstance(r["relative"], float)]
        return f"worst_variant={max(rel):.2f}x" if rel else "n/a"
    if name.startswith("fig11"):
        errs = [r["error"] for r in rows if "error" in r]
        return f"max_err={max(errs):.3f}" if errs else "n/a"
    if name.startswith("fig12"):
        return f"overlapped={all(r['overlapped'] for r in rows)}"
    if name.startswith("fig13"):
        return str(rows[-1]["loss"])
    if name.startswith("ckpt_policy"):
        by = {r["ckpt_policy"]: r for r in rows}
        sa, un = by["stage-aware"], by["uniform"]
        ratio = (sa["recompute_s"] / un["recompute_s"]
                 if un["recompute_s"] else 1.0)
        return (f"stage_aware_recompute_vs_uniform={ratio:.2f}x;"
                f"layers={sa['ckpt_layers']}vs{un['ckpt_layers']};"
                f"fits={sa['fits_memory']}")
    if name.startswith("paged_kv"):
        by = {r["row"]: r for r in rows}
        pc, cc = by["prefix_cache"], by["concurrency"]
        return (f"prefill_saving={pc['prefill_saving_frac']:.2f};"
                f"bitwise={pc['outputs_bitwise_equal']};"
                f"concurrency={cc['peak_concurrent_seqs']}"
                f"vs{cc['equiv_slots']}slots")
    if name.startswith("serving"):
        by = {r["prefill_mode"]: r for r in rows}
        il, se = by["interleaved"], by["serial"]
        blowup = (se["tpot_s_p95"] / il["tpot_s_p95"]
                  if il["tpot_s_p95"] else 1.0)
        return (f"serial_tpot_p95_vs_interleaved={blowup:.2f}x;"
                f"tok_s={il['tokens_per_s']};"
                f"occ={il['kv_occupancy']:.2f};"
                f"accept={il['spec_acceptance']:.2f}")
    if name.startswith("pipeline_bubble"):
        by = {r["schedule"]: r for r in rows}
        zb, fb = by["zero-bubble-h1"], by["gpipe-1f1b"]
        return (f"zb_realized={zb['realized_bubble']:.2f}"
                f"vs1f1b={fb['realized_bubble']:.2f};"
                f"zb_over_model={zb['realized_over_model']:.3f};"
                f"zb_speedup={zb['speedup_vs_1f1b']:.3f}x")
    if name.startswith("sp_axis"):
        by = {r["mix"]: r for r in rows}
        chk = by["check"]
        return (f"short={chk['short'][0]}@{chk['short'][1]};"
                f"long={chk['long'][0]}@{chk['long'][1]};"
                f"distinct={chk['distinct_sp_points']};"
                f"pin_bucket={by['short_uniform+pin']['pin_distinct_bucket']}")
    if name.startswith("replan"):
        r = rows[0]
        return (f"win={r['steady_state_win']:.3f};"
                f"swaps={r['swaps']}@{r['swap_step']};"
                f"fresh_in_tail={r['fresh_compiles_in_steady_state']};"
                f"comm_delta={r['meta']['calibration_deltas'].get('comm', 0)}")
    if name.startswith("cache"):
        summaries = [r for r in rows
                     if str(r.get("step", "")).startswith("summary")]
        return ";".join(f"q{s['cap_quantum']}:hit={s['hit_rate']:.2f}"
                        f",pad={s['padded_token_frac']:.2f}"
                        for s in summaries)
    return "ok"


if __name__ == "__main__":
    sys.exit(main())
