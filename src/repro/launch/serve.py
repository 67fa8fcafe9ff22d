"""Continuous-batching serving driver: the thin launcher for
``repro.serve.ServeEngine`` over a synthetic Poisson/lognormal request
trace (``data/synth.sample_request_trace`` presets).

Each engine step packs chunked-prefill segments and (speculative) decode
streams into ONE fixed-shape compiled program over a paged, sequence-sharded
KV pool; a second tiny program services copy-on-write page copies. The
compile cache therefore holds exactly two buckets — ``--passes 2`` replays
the identical trace and asserts the second pass compiles nothing.
``--system-prompt N`` prepends a shared N-token prefix to every request,
the regime the content-addressed prefix cache exists for (contrast with
``--no-prefix-cache`` to see the prefill-token saving). ``--cache-dir`` persists the
executable so even a fresh process warm-starts; ``--gc-max-age-s`` /
``--gc-max-bytes`` garbage-collect the store at startup.

CPU demo (4 fake devices):

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --reduced \\
      --requests 24 --passes 2 --k 2 --stats-json serve-stats.json
"""

from __future__ import annotations

import argparse
import sys


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh", default="2x2", help="DPxSP, e.g. 2x2")
    ap.add_argument("--devices", type=int, default=4)
    # trace
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--trace", default="github",
                    help="length preset (github/commoncrawl/uniform)")
    ap.add_argument("--context-limit", type=int, default=96,
                    help="max prompt length the trace samples")
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--arrival-rate", type=float, default=2.0,
                    help="Poisson arrivals per simulated second")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--passes", type=int, default=1,
                    help="replay the identical trace N times; pass 2+ must "
                         "report zero fresh compiles (closed bucket set)")
    # engine geometry (the closed two-bucket compile-cache set)
    ap.add_argument("--items", type=int, default=4,
                    help="packed chunk items per engine step")
    ap.add_argument("--cap-t", type=int, default=32,
                    help="tokens per item (= max prefill chunk)")
    ap.add_argument("--pages", type=int, default=0,
                    help="KV pages pool-wide; 0 = auto (6 concurrent "
                         "max-context requests, rounded to the model axis)")
    ap.add_argument("--page-sz", type=int, default=16,
                    help="cache rows per KV page")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable content-addressed page sharing (the "
                         "prefix-cache OFF baseline the benchmark contrasts)")
    ap.add_argument("--system-prompt", type=int, default=0,
                    help="prepend a shared system prompt of this many "
                         "tokens to every trace request (prefix-cache "
                         "regime; 0 = off)")
    ap.add_argument("--k", type=int, default=1,
                    help="decode tokens per stream per step (speculative "
                         "draft width; k=1 is plain greedy)")
    # scheduling policy (no recompile across these)
    ap.add_argument("--prefill-mode", default="interleaved",
                    choices=["interleaved", "serial"],
                    help="'serial' = naive stop-the-world prefill baseline")
    ap.add_argument("--decode-budget", type=int, default=0,
                    help="decode tokens per step (0 = auto)")
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="prefill tokens per step (0 = auto)")
    # persistence
    ap.add_argument("--cache-dir", default="",
                    help="persistent compile-cache directory: a serving "
                         "restart warm-starts the engine bucket instead of "
                         "recompiling")
    ap.add_argument("--gc-max-age-s", type=float, default=0.0,
                    help="cache-store gc at startup: drop entries not "
                         "loaded in this many seconds (0 = off)")
    ap.add_argument("--gc-max-bytes", type=int, default=0,
                    help="cache-store gc at startup: shrink the store to "
                         "this many payload bytes (0 = off)")
    ap.add_argument("--stats-json", default="",
                    help="write per-pass engine stats + cache/store stats "
                         "to this JSON file (CI artifact)")
    ap.add_argument("--telemetry-dir", default="",
                    help="spill a telemetry timeline (engine TTFT/TPOT/"
                         "occupancy events) to this directory as JSONL")
    ap.add_argument("--verify", type=int, default=0,
                    help="cross-check the first N requests' output ids "
                         "against the one-shot reference path")
    ap.add_argument("--lint", default="warn",
                    choices=["off", "warn", "error"],
                    help="program auditor on the engine's cold compile: "
                         "'warn' logs findings, 'error' aborts before a "
                         "hazardous executable enters the cache")
    return ap


def main():
    args = build_parser().parse_args()
    import os
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={args.devices}")
    from repro.launch.mesh import make_mesh
    from repro.telemetry import atomic_write_json

    dp, ds = (int(x) for x in args.mesh.split("x"))
    rc, out = serve(args, make_mesh((dp, ds), ("data", "model")))
    # the stats artifact is written even on a failed run — CI diagnoses
    # exactly the failing case from it. Atomic (tmp + os.replace): an
    # external scraper can never read a torn file
    if args.stats_json and out is not None:
        atomic_write_json(args.stats_json, out)
    if out is not None and out["error"]:
        print(f"error: {out['error']}", file=sys.stderr)
        return rc
    if rc == 0:
        print("serve OK")
    return rc


def serve(args: argparse.Namespace, mesh):
    """Run the trace ``args`` describes (``build_parser()``'s fields) on
    ``mesh`` in this process. Returns ``(rc, stats)``; ``stats`` is None
    when the trace is refused before the engine starts."""
    import jax.numpy as jnp

    from repro.configs import get_arch
    from repro.data import sample_request_trace
    from repro.runtime import CacheStore, store_fingerprint
    from repro.runtime.compile_cache import CompileCache
    from repro.serve import (EngineConfig, Request, ServeEngine,
                             one_shot_generate)
    from repro.telemetry import StepTimeline

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    ds = mesh.shape["model"]

    page_sz = args.page_sz
    pages_per_seq = -(-(args.context_limit + args.max_new) // page_sz)
    if args.pages:
        n_pages = args.pages
    else:
        # auto: room for ~6 concurrent max-context requests, rounded up to
        # a multiple of the model axis (the pool is sequence-sharded)
        n_pages = -(-(6 * pages_per_seq) // ds) * ds
    trace = sample_request_trace(args.trace, args.requests,
                                 args.context_limit, cfg.spec.vocab,
                                 seed=args.seed,
                                 arrival_rate=args.arrival_rate,
                                 max_new_tokens=args.max_new,
                                 system_prompt_len=args.system_prompt)
    # admission validation UP FRONT: the old driver silently truncated an
    # over-long prompt's context; the engine (and this check) reject it
    longest = max(len(t["prompt"]) for t in trace)
    if longest + args.max_new > pages_per_seq * page_sz:
        print(f"error: longest sampled prompt ({longest}) + --max-new "
              f"({args.max_new}) exceeds the per-request page budget "
              f"({pages_per_seq} pages x {page_sz} rows); raise "
              f"--page-sz or lower --context-limit — context is never "
              f"silently truncated",
              file=sys.stderr)
        return 2, None

    store = gc_report = None
    if args.cache_dir:
        store = CacheStore(args.cache_dir,
                           store_fingerprint(mesh, spec=cfg.spec,
                                             compute_dtype=jnp.float32),
                           log=print)
        gc_report = store.gc(
            max_age_s=args.gc_max_age_s or None,
            max_bytes=args.gc_max_bytes or None)
    from repro.launch.mesh import latency_hiding_active
    from repro.lint import make_cache_lint
    cache = CompileCache(name="serve-engine", log=print, store=store,
                         lint=make_cache_lint(
                             args.lint, log=print,
                             latency_hiding=latency_hiding_active()))

    econf = EngineConfig(
        n_items=args.items, cap_t=args.cap_t, n_pages=n_pages,
        page_sz=page_sz, pages_per_seq=pages_per_seq, k=args.k,
        prefix_cache=not args.no_prefix_cache,
        decode_token_budget=args.decode_budget or None,
        prefill_token_budget=args.prefill_budget or None,
        prefill_mode=args.prefill_mode)

    def requests():
        return [Request(req_id=i, prompt=t["prompt"],
                        max_new_tokens=t["max_new_tokens"],
                        arrival=t["arrival"]) for i, t in enumerate(trace)]

    timeline = StepTimeline(spill_dir=args.telemetry_dir or None,
                            name="serve")
    passes = []
    params = None
    rc = 0
    error = None
    for p in range(max(1, args.passes)):
        try:
            engine = ServeEngine(cfg, mesh, econf, params=params,
                                 param_dtype=jnp.float32, cache=cache,
                                 seed=args.seed, log=print,
                                 timeline=timeline)
        except NotImplementedError as e:
            # SSM/hybrid, enc-dec and MLA archs have no engine path yet;
            # their pipelined one-shot decode step (decode_step_fn) is
            # still exercised by the dryrun decode cells
            rc, error = 5, (f"arch {args.arch!r} is not servable by the "
                            f"continuous-batching engine: {e}")
            break
        params = engine.params
        misses_before = cache.stats.misses
        results = engine.run(requests())
        st = engine.stats()
        st["pass"] = p
        st["fresh_compiles"] = cache.stats.misses - misses_before
        # per-request output ids so CI can assert cache-on == cache-off
        # bitwise (the prefix cache must never change what comes out)
        st["outputs"] = {int(r): results[r].output_ids
                        for r in sorted(results)}
        passes.append(st)
        print(f"[pass {p}] completed={st['completed']}/{len(trace)} "
              f"steps={st['steps']} tok/s={st['tokens_per_s']} "
              f"ttft_p95={st['ttft_s_p95']}s "
              f"occupancy={st['kv_pool']['mean_occupancy']} "
              f"prefix_hits={st['kv_pool']['prefix_hit_rows']} "
              f"prefill_fed={st['prefill_tokens_fed']} "
              f"accept={st['speculative']['acceptance_rate']} "
              f"fresh_compiles={st['fresh_compiles']}")
        if p > 0 and st["fresh_compiles"]:
            rc, error = 3, ("pass > 0 compiled fresh executables — the "
                            "engine bucket set is not closed")
            break
        if p == 0 and args.verify:
            n_v = min(args.verify, len(trace))
            ref = one_shot_generate(cfg, mesh, params,
                                    [t["prompt"] for t in trace[:n_v]],
                                    args.max_new)
            for i in range(n_v):
                got = results[i].output_ids
                if got != ref[i]:
                    rc, error = 4, (f"request {i} engine ids {got} != "
                                    f"one-shot ids {ref[i]}")
                    break
            if rc:
                break
            print(f"[verify] {n_v} requests match the one-shot path")

    print(f"[compile-cache] {cache.stats.summary()}")
    out = {"config": vars(args), "passes": passes,
           "compile_cache": cache.stats.as_dict(), "error": error,
           "telemetry": timeline.snapshot()}
    timeline.close()
    if store is not None:
        rep = store.report()
        out["cache_store"] = rep
        out["cache_store_gc"] = gc_report
        print(f"[cache-store] {rep}")
    return rc, out


if __name__ == "__main__":
    sys.exit(main())
