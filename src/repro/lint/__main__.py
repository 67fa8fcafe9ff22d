"""Offline pipeline-program audit: ``python -m repro.lint``.

Three audit surfaces, combinable in one invocation:

* ``--arch NAME`` / ``--all`` — plan the arch's workload at a tiny CPU
  geometry (the same planner path real training takes), run the plan
  passes (tick coverage, ckpt table, ppermute ring, bucket-key
  completeness), then AOT trace/lower/compile the train step and run the
  program passes over jaxpr + StableHLO + HLO. ``--target serve`` audits
  the continuous-batching engine step instead (HLO tier only — the
  engine builder returns a ``Compiled``).
* ``--cache-dir DIR`` — jax-free integrity audit of a persistent
  :class:`~repro.runtime.cache_store.CacheStore` (orphan sidecars,
  truncated payloads, sha mismatches, stale fingerprints).
* ``--lower`` — upgrade the bucket-key completeness pass from
  key-inequality to lowering-inequality (each perturbed plan is actually
  lowered; slower but proves distinct keys name distinct programs).

Exit status: 0 when clean (or mode ``warn``), 1 when ``--lint error``
and any finding survived. CI runs representative train + serve buckets
at ``--lint error`` against a committed zero-findings baseline.

Usage:
  PYTHONPATH=src python -m repro.lint --arch gemma3-1b --target train,serve
  PYTHONPATH=src python -m repro.lint --all --json lint-report.json
  PYTHONPATH=src python -m repro.lint --cache-dir runs/ckpt_compile_cache
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _parse_args(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m repro.lint",
        description="static plan + program audit of the EPP pipeline")
    ap.add_argument("--arch", default=None,
                    help="registry arch(es) to audit, comma-separated "
                         "(configs/registry.py)")
    ap.add_argument("--all", action="store_true",
                    help="audit every registry arch")
    ap.add_argument("--target", default="train",
                    help="comma list of program surfaces: train,serve")
    ap.add_argument("--mesh", default="2x2", help="DPxSP, e.g. 2x2")
    ap.add_argument("--devices", type=int, default=4,
                    help="placeholder CPU device count")
    ap.add_argument("--lengths", default="256,256,128,384",
                    help="comma list of sequence lengths the planner packs")
    ap.add_argument("--bucket-rounding", type=int, default=64)
    ap.add_argument("--schedule", default=None,
                    help="pin a schedule backend (default: planner picks)")
    ap.add_argument("--split-bwd", default="auto",
                    choices=["auto", "on", "off"])
    ap.add_argument("--dtype", default="bfloat16",
                    choices=["bfloat16", "float32"])
    ap.add_argument("--lower", action="store_true",
                    help="bucket-key completeness compares actual "
                         "lowerings, not just key inequality (slow)")
    ap.add_argument("--plan-only", action="store_true",
                    help="skip the AOT compile; audit plan invariants only")
    ap.add_argument("--cache-dir", default=None,
                    help="audit a persistent compile-cache store directory "
                         "(jax-free; combinable with --arch/--all)")
    ap.add_argument("--lint", default="warn", choices=["warn", "error"],
                    help="'error' exits 1 on any finding")
    ap.add_argument("--json", default="",
                    help="write the full report to this JSON file")
    return ap.parse_args(argv)


def _audit_cache_dir(path: str) -> dict:
    from repro.runtime.cache_store import CacheStore
    # audit() is fingerprint-blind, so any fingerprint works here
    store = CacheStore(path, fingerprint={"purpose": "lint-audit"})
    rows = store.audit()
    bad = [r for r in rows if r["problems"]]
    return {"dir": path, "entries": len(rows),
            "corrupt": len(bad), "rows": rows,
            "findings": [f"{r['entry']}: {p}" for r in bad
                         for p in r["problems"]]}


def _train_lower_fn(cfg, mesh, dtype_name):
    """lower_fn(plan_variant, key_kwargs) -> StableHLO text, for the
    lowering tier of the bucket-key completeness pass."""
    import jax
    import jax.numpy as jnp

    from repro.optim import init_opt_state
    from repro.runtime import TrainStepBuilder, batch_struct, make_geometry
    from repro.runtime.sharding import mesh_axis_names

    _, data, model = mesh_axis_names(mesh)
    d_s = mesh.shape[model]

    def lower_fn(plan, key_kwargs):
        key = plan.bucket_key(d_s, **key_kwargs)
        dt = jnp.bfloat16 if key.dtype == "bfloat16" else jnp.float32
        l_max, table, _ = plan.ckpt_policy(key.n_chunks)
        geom = make_geometry(cfg, mesh, n_chunks=key.n_chunks, cap=key.cap,
                             ctx_cap=key.ctx_cap, l_ckpt=l_max,
                             compute_dtype=dt, schedule=key.schedule,
                             v_stages=key.v_stages, ckpt_table=table,
                             split_bwd=key.split_bwd)
        builder = TrainStepBuilder(cfg, mesh, geom, param_dtype=dt)
        params_shape = builder.abstract_params()
        opt_shape = jax.eval_shape(init_opt_state, params_shape)
        bstruct = batch_struct(geom, 1)
        return builder.build(params_shape).lower(
            params_shape, opt_shape, None, bstruct).as_text()

    return lower_fn


def _audit_train(cfg, mesh, plan, args) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.lint.runner import ProgramArtifacts, run_program_checks
    from repro.optim import init_opt_state
    from repro.runtime import TrainStepBuilder, batch_struct, make_geometry
    from repro.runtime.sharding import mesh_axis_names

    _, data, model = mesh_axis_names(mesh)
    d_s = mesh.shape[model]
    key = plan.bucket_key(d_s, split_bwd=args.split_bwd, dtype=args.dtype)
    dt = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    l_max, table, _ = plan.ckpt_policy(key.n_chunks)
    geom = make_geometry(cfg, mesh, n_chunks=key.n_chunks, cap=key.cap,
                         ctx_cap=key.ctx_cap, l_ckpt=l_max,
                         compute_dtype=dt, schedule=key.schedule,
                         v_stages=key.v_stages, ckpt_table=table,
                         split_bwd=key.split_bwd)
    builder = TrainStepBuilder(cfg, mesh, geom, param_dtype=dt)
    params_shape = builder.abstract_params()
    opt_shape = jax.eval_shape(init_opt_state, params_shape)
    bstruct = batch_struct(geom, 1)
    traced = builder.build(params_shape).trace(params_shape, opt_shape,
                                              None, bstruct)
    lowered = traced.lower()
    compiled = lowered.compile()
    art = ProgramArtifacts(key=key, jaxpr=traced.jaxpr,
                           stablehlo=lowered.as_text(),
                           hlo=compiled.as_text(),
                           platform=jax.default_backend())
    report = run_program_checks(art)
    return {"key": repr(key), "report": report}


def _audit_serve(cfg, mesh, args) -> dict:
    import jax

    from repro.lint.runner import ProgramArtifacts, run_program_checks
    from repro.runtime.compile_cache import (engine_bucket_key,
                                             engine_copy_bucket_key)
    from repro.runtime.serve_step import (EngineStepBuilder,
                                          make_engine_geometry)

    geom = make_engine_geometry(cfg, mesh, n_items=4, cap_t=32, n_pages=8,
                                page_sz=8, k=1)
    builder = EngineStepBuilder(cfg, mesh, geom)
    compiled = builder.build()
    key = engine_bucket_key(geom)
    art = ProgramArtifacts(key=key, hlo=compiled.as_text(),
                           platform=jax.default_backend())
    report = run_program_checks(art)
    # the serve bucket set has a second member: the COW page-copy program
    copy_key = engine_copy_bucket_key(geom)
    copy_art = ProgramArtifacts(key=copy_key,
                                hlo=builder.build_copy().as_text(),
                                platform=jax.default_backend())
    return {"key": repr(key), "report": report,
            "copy_key": repr(copy_key),
            "copy_report": run_program_checks(copy_art)}


def _report_dict(report) -> dict:
    return report.as_dict()


def main(argv=None) -> int:
    args = _parse_args(argv)
    targets = [t for t in args.target.split(",") if t]
    bad_targets = set(targets) - {"train", "serve"}
    if bad_targets:
        print(f"error: unknown --target {sorted(bad_targets)} "
              f"(valid: train, serve)", file=sys.stderr)
        return 2
    if not (args.arch or args.all or args.cache_dir):
        print("error: nothing to audit — pass --arch NAME, --all, "
              "and/or --cache-dir DIR", file=sys.stderr)
        return 2

    out = {"subjects": [], "cache_store": None}
    n_findings = 0
    n_errors = 0

    if args.cache_dir:
        store_audit = _audit_cache_dir(args.cache_dir)
        out["cache_store"] = {k: store_audit[k]
                              for k in ("dir", "entries", "corrupt",
                                        "findings")}
        for f in store_audit["findings"]:
            print(f"[lint] error: cache-store: {f}")
        n_findings += len(store_audit["findings"])
        n_errors += len(store_audit["findings"])
        print(f"[cache-store] {store_audit['entries']} entries, "
              f"{store_audit['corrupt']} corrupt")

    if args.arch or args.all:
        # the placeholder-device flag must precede the first jax import
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags +
                f" --xla_force_host_platform_device_count={args.devices}"
            ).strip()
        from repro.configs import arch_names, get_arch
        from repro.core import (ClusterSpec, CostModel, PlannerConfig,
                                plan_batch)
        from repro.launch.mesh import make_mesh
        from repro.lint.plan_checks import run_plan_checks

        names = arch_names() if args.all else args.arch.split(",")
        d_p, d_s = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((d_p, d_s), ("data", "model"))
        lengths = [int(x) for x in args.lengths.split(",")]
        key_kwargs = {"split_bwd": args.split_bwd, "dtype": args.dtype}

        for name in names:
            cfg = get_arch(name).reduced()
            cm = CostModel(cfg.spec, ClusterSpec(d_p=d_p, d_s=d_s))
            plan = plan_batch(
                cm, lengths,
                PlannerConfig(bucket_rounding=args.bucket_rounding,
                              schedule=args.schedule))
            lower_fn = None
            if args.lower and not cfg.spec.is_encoder_decoder:
                lower_fn = _train_lower_fn(cfg, mesh, args.dtype)
            plan_rep = run_plan_checks(plan, d_s, d_p,
                                       key_kwargs=key_kwargs,
                                       lower_fn=lower_fn)
            subject = {"arch": name, "schedule": plan.schedule,
                       "v_stages": plan.v_stages,
                       "plan": _report_dict(plan_rep), "programs": {}}
            reports = [plan_rep]

            if not args.plan_only:
                if "train" in targets:
                    if cfg.spec.is_encoder_decoder:
                        subject["programs"]["train"] = {
                            "skipped": "enc-dec archs compile through the "
                                       "dryrun cell, not TrainStepBuilder"}
                    else:
                        res = _audit_train(cfg, mesh, plan, args)
                        subject["programs"]["train"] = {
                            "key": res["key"],
                            **_report_dict(res["report"])}
                        reports.append(res["report"])
                if "serve" in targets:
                    try:
                        res = _audit_serve(cfg, mesh, args)
                    except NotImplementedError as e:
                        subject["programs"]["serve"] = {
                            "skipped": f"not servable: {e}"}
                    else:
                        subject["programs"]["serve"] = {
                            "key": res["key"],
                            **_report_dict(res["report"])}
                        subject["programs"]["serve-copy"] = {
                            "key": res["copy_key"],
                            **_report_dict(res["copy_report"])}
                        reports.append(res["report"])
                        reports.append(res["copy_report"])

            for rep in reports:
                n_findings += len(rep.findings)
                n_errors += len(rep.errors)
                for f in rep.findings:
                    print(f"[lint] {name}: {f}")
            summaries = " | ".join(r.summary() for r in reports)
            print(f"[{name}] {summaries}")
            out["subjects"].append(subject)

    out["total_findings"] = n_findings
    out["total_errors"] = n_errors
    out["mode"] = args.lint
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(out, fh, indent=1, default=str)
        print(f"[report] wrote {args.json}")
    verdict = ("clean" if n_findings == 0 else
               f"{n_findings} finding(s) ({n_errors} error(s))")
    print(f"[lint] {verdict}")
    if args.lint == "error" and n_findings:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
