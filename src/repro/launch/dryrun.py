import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST precede every other import (jax locks the device count on first
# init). The 512 placeholder CPU devices exist only in this process; tests
# and benches see the single real device.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. runs the InfiniPipe PLANNER (host-side) on the shape's workload to get
     the chunk geometry + the ILP checkpointing level — the same path real
     training takes;
  2. builds the jit'd step (train_step / prefill / decode) for the
     production mesh and calls ``.lower().compile()`` on ShapeDtypeStructs
     (no allocation);
  3. records ``memory_analysis()`` / ``cost_analysis()`` + an HLO collective
     scan + analytic collective volumes into a JSON cache.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k
  python -m repro.launch.dryrun --all [--multipod] [--out runs/dryrun]
"""

import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

import numpy as np


def _cell_skip_reason(cfg, shape) -> str:
    if shape.needs_subquadratic and not cfg.supports_long_decode:
        return ("skipped: pure full-attention arch at 500K decode "
                "(DESIGN.md §4.1)")
    return ""


from repro.launch.analysis import analytic_collectives, collective_scan
from repro.runtime.compile_cache import CompileCache

# one executable per geometry bucket across cells: identical buckets
# (e.g. two shapes landing on the same plan geometry) compile once.
# Bounded: compiled 256+-device programs are large, and cross-cell hits
# are the exception — don't retain the whole sweep in host memory.
# Cost-aware eviction keeps the expensive-to-recompile cells resident.
_CELL_CACHE = CompileCache(name="dryrun-cell", capacity=2, eviction="cost")


def attach_cell_store(cache_dir: str) -> None:
    """Back the cell cache with a persistent store: re-running a sweep
    (or resuming an interrupted one) warm-starts compiled cells. The cell
    key already carries arch/shape/mesh, so the fingerprint only pins the
    process topology (jax version, backend, device count)."""
    from repro.runtime.cache_store import CacheStore, store_fingerprint
    _CELL_CACHE.store = CacheStore(cache_dir, store_fingerprint(),
                                   log=print)


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: Path,
             remat_override=None, note: str = "",
             zero3_mode: str = "per_tick",
             ckpt_policy: str = "stage-aware",
             sp_policy: str = "auto", sp_degree: int = 0) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs import SHAPES, get_arch
    from repro.core import ClusterSpec, CostModel, PlannerConfig, plan_batch
    from repro.launch.mesh import make_production_mesh
    from repro.runtime import TrainStepBuilder, batch_struct, make_geometry
    from repro.runtime.serve_step import (decode_state_specs,
                                          decode_state_struct,
                                          decode_step_fn,
                                          make_decode_geometry)
    from repro.runtime.sharding import mesh_axis_names, shard_dim_tree
    from repro.runtime.pipeline import pipeline_loss_fn

    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    t0 = time.perf_counter()
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16", "note": note}
    reason = _cell_skip_reason(cfg, shape)
    if reason:
        rec["status"] = "skipped"
        rec["reason"] = reason
        return rec
    if cfg.spec.is_encoder_decoder and shape.kind == "decode":
        pass  # decoder-side decode is supported

    mesh = make_production_mesh(multi_pod=multi_pod)
    pod, data, model = mesh_axis_names(mesh)
    n_pods = mesh.shape[pod] if pod else 1
    d_p, d_s = mesh.shape[data], mesh.shape[model]
    per_pod_batch = max(1, shape.global_batch // n_pods)

    if cfg.spec.is_encoder_decoder and shape.kind in ("train", "prefill"):
        return _run_encdec_cell(rec, cfg, shape, mesh, per_pod_batch, t0,
                                ckpt_policy=ckpt_policy)

    if shape.kind in ("train", "prefill"):
        cm = CostModel(cfg.spec, ClusterSpec(d_p=d_p, d_s=d_s,
                                             n_pods=n_pods))
        lengths = [shape.seq_len] * per_pod_batch
        remat_mode = ("stage_aware" if ckpt_policy == "stage-aware"
                      else "uniform")
        # prefill cells pin the full model axis: the token-sharded greedy
        # fold assumes every device owns a distinct token shard, which
        # sub-degree replication (d_s_eff < d_s) breaks — the planner's
        # SP sweep only applies to train cells.
        cell_sp_degree = d_s if shape.kind == "prefill" else sp_degree
        plan = plan_batch(cm, lengths, PlannerConfig(remat_mode=remat_mode,
                                                     sp_policy=sp_policy,
                                                     sp_degree=cell_sp_degree))
        chunks = [c for p in plan.pipelines for c in p.chunks]
        cap = ((plan.chunk_capacity + d_s - 1) // d_s) * d_s
        max_ctx = max((c.context for c in chunks), default=0)
        ctx_cap = max_ctx + cap
        # per-stage remat axis of the sweep: an explicit --remat override
        # forces a uniform depth; otherwise the plan's canonical policy
        # (stage-aware => the per-(stage, chunk) vector) is baked in
        if remat_override is not None:
            l_ckpt, table, digest = remat_override, None, \
                f"u{remat_override}"
        else:
            l_ckpt, table, digest = plan.ckpt_policy(len(chunks))
        geom = make_geometry(cfg, mesh, n_chunks=len(chunks), cap=cap,
                             ctx_cap=ctx_cap, l_ckpt=l_ckpt,
                             zero3_mode=zero3_mode,
                             schedule=plan.schedule,
                             v_stages=plan.v_stages,
                             ckpt_table=table,
                             sp_policy=(plan.sp.policy
                                        if plan.sp is not None else None),
                             sp_degree=(plan.sp.d_s_eff
                                        if plan.sp is not None else 0))
        rec["plan"] = {"K": plan.k_split, "n_chunks": len(chunks),
                       "cap": cap, "ctx_cap": ctx_cap, "l_ckpt": l_ckpt,
                       "ckpt_policy": ckpt_policy, "ckpt_digest": digest,
                       "l_ckpt_stage": plan.ckpt_per_stage_max(),
                       "schedule": plan.schedule, "v_stages": plan.v_stages,
                       "sp_policy": (plan.sp.policy
                                     if plan.sp is not None else "auto"),
                       "d_s_eff": (plan.sp.d_s_eff
                                   if plan.sp is not None else d_s),
                       "pipelines": len(plan.pipelines),
                       "est_time_s": plan.est_total_time,
                       "solve_time_s": plan.solve_time}
        builder = TrainStepBuilder(cfg, mesh, geom)
        params_shape = builder.abstract_params()
        pspecs, ospecs, bspecs = builder.specs(params_shape)
        bstruct = batch_struct(geom, n_pods)
        if shape.kind == "train":
            step = builder.build(params_shape)
            opt_shape = jax.eval_shape(
                lambda p: __import__("repro.optim", fromlist=["x"]
                                     ).init_opt_state(p), params_shape)
            lowered = step.lower(params_shape, opt_shape, None, bstruct)
        else:
            shard_dims = shard_dim_tree(params_shape["stages"], d_s)
            fn = pipeline_loss_fn(cfg, geom, shard_dims, pod_axis=pod,
                                  data_axis=data, model_axis=model,
                                  mode="prefill")
            def prefill(params, batch):
                if pod:
                    batch = jax.tree.map(lambda x: x[0], batch)
                return fn(params, batch)
            mapped = jax.shard_map(prefill, mesh=mesh,
                                   in_specs=(pspecs, bspecs),
                                   out_specs=(P(None, model),
                                              _ctx_specs(cfg, geom,
                                                         pod, data, model)),
                                   check_vma=False)
            lowered = jax.jit(mapped).lower(params_shape, bstruct)
    else:  # decode
        geom = make_decode_geometry(cfg, mesh, batch_per_pod=per_pod_batch,
                                    cache_len=shape.seq_len)
        rec["plan"] = {"n_micro": geom.n_micro, "bm": geom.bm,
                       "cache_len": geom.cache_len}
        if cfg.spec.is_encoder_decoder:
            from repro.models import EncDecLM
            from repro.runtime.encdec_pipeline import \
                prepare_encdec_decode_params
            from repro.runtime.train_step import param_pspecs
            raw_shape = jax.eval_shape(
                lambda k: EncDecLM(cfg).init(k, jnp.float32),
                jax.random.PRNGKey(0))
            params_shape = jax.eval_shape(
                lambda r: prepare_encdec_decode_params(cfg, r, d_p, d_s),
                raw_shape)
            pspecs = param_pspecs(cfg, params_shape, mesh)
        else:
            builder = TrainStepBuilder(cfg, mesh, make_geometry(
                cfg, mesh, n_chunks=1, cap=d_s, ctx_cap=d_s))
            params_shape = builder.abstract_params()
            pspecs, _, _ = builder.specs(params_shape)
        shard_dims = shard_dim_tree(params_shape["stages"], d_s)
        fn = decode_step_fn(cfg, geom, shard_dims, pod_axis=pod,
                            data_axis=data, model_axis=model)
        sspecs = decode_state_specs(cfg, geom, pod=pod, data=data,
                                    model=model)
        mapped = jax.shard_map(fn, mesh=mesh,
                               in_specs=(pspecs, sspecs),
                               out_specs=(P(), sspecs),
                               check_vma=False)
        sstruct = decode_state_struct(cfg, geom, n_pods)
        lowered = jax.jit(mapped, donate_argnums=(1,)).lower(
            params_shape, sstruct)

    t_lower = time.perf_counter()
    compiled = _CELL_CACHE.get(
        (arch, shape.kind, geom, zero3_mode, rec["mesh"]), lowered.compile)
    t_compile = time.perf_counter()

    mem = compiled.memory_analysis()
    cost = dict(compiled.cost_analysis())
    hlo = compiled.as_text()
    rec.update({
        "status": "ok",
        "lower_s": round(t_lower - t0, 2),
        "compile_s": round(t_compile - t_lower, 2),
        "memory_analysis": {
            k: int(getattr(mem, k, 0)) for k in
            ("temp_size_in_bytes", "argument_size_in_bytes",
             "output_size_in_bytes", "alias_size_in_bytes",
             "generated_code_size_in_bytes")},
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float))
                          and k in ("flops", "bytes accessed",
                                    "bytes accessed0{}", "transcendentals",
                                    "utilization operand 0 {}")},
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "hlo_collectives_static": collective_scan(hlo),
        "n_devices": int(np.prod(list(mesh.shape.values()))),
        "compile_cache": _CELL_CACHE.stats.as_dict(),
    })
    kind = shape.kind
    gg = geom
    rec["analytic_collectives"] = analytic_collectives(cfg, gg, kind)
    rec["geometry"] = {
        k: getattr(gg, k) for k in
        (("n_chunks", "cap", "ctx_cap", "l_ckpt", "layers_per_stage",
          "policy", "d_s_eff", "zero3_mode")
         if kind in ("train", "prefill") else
         ("n_micro", "cache_len", "layers_per_stage"))}
    return rec


def _run_encdec_cell(rec, cfg, shape, mesh, per_pod_batch, t0,
                     ckpt_policy: str = "stage-aware"):
    """seamless-m4t train/prefill: the stage-split enc-dec pipeline."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core import ClusterSpec, CostModel, PlannerConfig, plan_batch
    from repro.models import EncDecLM
    from repro.optim import AdamWConfig, adamw_update, init_opt_state
    from repro.runtime.encdec_pipeline import (encdec_batch_struct,
                                               encdec_pipeline_loss_fn,
                                               make_encdec_geometry,
                                               prepare_encdec_params)
    from repro.runtime.sharding import (batch_specs, mesh_axis_names,
                                        shard_dim_tree,
                                        stage_param_specs)
    import time as _time

    pod, data, model = mesh_axis_names(mesh)
    n_pods = mesh.shape[pod] if pod else 1
    d_p, d_s = mesh.shape[data], mesh.shape[model]
    cm = CostModel(cfg.spec, ClusterSpec(d_p=d_p, d_s=d_s, n_pods=n_pods))
    lengths = [shape.seq_len] * per_pod_batch
    # encoder is pack-only: force K=1 (DESIGN.md §4 — splitting a
    # bidirectional encoder changes the math); decoder chunks follow.
    remat_mode = ("stage_aware" if ckpt_policy == "stage-aware"
                  else "uniform")
    # v_stages=1 pin: the grouped enc+dec stacking has no interleaved
    # placement, so restrict the schedule pick to single-virtual-stage
    # backends — and actually RUN the pick (the compiled cell must be the
    # schedule the recorded plan stats describe)
    # enc-dec geometry does not carry the SP axis — pin the plan to the
    # full model axis so its recorded stats match the compiled cell.
    plan = plan_batch(cm, lengths, PlannerConfig(fixed_k=1,
                                                 remat_mode=remat_mode,
                                                 v_stages=1,
                                                 sp_degree=d_s))
    chunks = [c for p in plan.pipelines for c in p.chunks]
    cap = ((plan.chunk_capacity + d_s - 1) // d_s) * d_s
    l_max, table, digest = plan.ckpt_policy(len(chunks))
    geom = make_encdec_geometry(cfg, mesh, n_chunks=len(chunks), cap=cap,
                                cap_enc=cap, ctx_cap=cap + d_s,
                                l_ckpt=l_max, ckpt_table=table,
                                schedule=plan.schedule)
    rec["plan"] = {"K": plan.k_split, "n_chunks": len(chunks), "cap": cap,
                   "schedule": plan.schedule,
                   "ckpt_policy": ckpt_policy, "ckpt_digest": digest,
                   "l_ckpt_stage": plan.ckpt_per_stage_max()}

    raw_shape = jax.eval_shape(
        lambda k: EncDecLM(cfg).init(k, jnp.float32), jax.random.PRNGKey(0))
    params_shape = jax.eval_shape(
        lambda r: prepare_encdec_params(cfg, r, geom), raw_shape)
    pspecs = {
        "stages": stage_param_specs(params_shape["stages"], d_s, pod=pod,
                                    data=data, model=model),
        "embed": P(model, None),
        "enc_norm": P(model) if cfg.spec.d_model % d_s == 0 else P(),
        "final_norm": P(model) if cfg.spec.d_model % d_s == 0 else P(),
    }
    shard_dims = shard_dim_tree(params_shape["stages"], d_s)
    bstruct = encdec_batch_struct(geom, cfg, n_pods)
    bspecs = batch_specs(bstruct, pod=pod, model=model)
    fn = encdec_pipeline_loss_fn(cfg, geom, shard_dims, pod_axis=pod,
                                 data_axis=data, model_axis=model)

    if shape.kind == "train":
        ospecs = {"master": pspecs, "m": pspecs, "v": pspecs, "step": P()}
        acfg = AdamWConfig()

        def step(params, opt, batch):
            if pod:
                batch = jax.tree.map(lambda x: x[0], batch)

            def obj(p):
                loss, n = fn(p, batch)
                return loss, n
            (loss, n), grads = jax.value_and_grad(obj, has_aux=True)(params)
            for name in ("embed", "enc_norm", "final_norm"):
                grads[name] = jax.lax.psum(grads[name], data)
            if pod:
                grads = jax.lax.psum(grads, pod)
                loss = jax.lax.psum(loss, pod)
                n = jax.lax.psum(n, pod)
            new_p, new_o, _ = adamw_update(acfg, params, grads, opt,
                                           grad_scale=1.0 / jnp.maximum(n, 1),
                                           gnorm=jnp.float32(1.0))
            return new_p, new_o, loss / jnp.maximum(n, 1)

        mapped = jax.shard_map(step, mesh=mesh,
                               in_specs=(pspecs, ospecs, bspecs),
                               out_specs=(pspecs, ospecs, P()),
                               check_vma=False)
        opt_shape = jax.eval_shape(init_opt_state, params_shape)
        lowered = jax.jit(mapped, donate_argnums=(0, 1)).lower(
            params_shape, opt_shape, bstruct)
    else:
        def fwd(params, batch):
            if pod:
                batch = jax.tree.map(lambda x: x[0], batch)
            return fn(params, batch)
        mapped = jax.shard_map(fwd, mesh=mesh, in_specs=(pspecs, bspecs),
                               out_specs=(P(), P()), check_vma=False)
        lowered = jax.jit(mapped).lower(params_shape, bstruct)

    t_lower = _time.perf_counter()
    compiled = _CELL_CACHE.get(
        (rec["arch"], shape.kind, geom, "encdec", rec["mesh"]),
        lowered.compile)
    t_compile = _time.perf_counter()
    mem = compiled.memory_analysis()
    cost = dict(compiled.cost_analysis())
    hlo = compiled.as_text()
    import numpy as _np
    rec.update({
        "status": "ok",
        "lower_s": round(t_lower - t0, 2),
        "compile_s": round(t_compile - t_lower, 2),
        "memory_analysis": {
            k: int(getattr(mem, k, 0)) for k in
            ("temp_size_in_bytes", "argument_size_in_bytes",
             "output_size_in_bytes", "alias_size_in_bytes",
             "generated_code_size_in_bytes")},
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "hlo_collectives_static": collective_scan(hlo),
        "n_devices": int(_np.prod(list(mesh.shape.values()))),
        "compile_cache": _CELL_CACHE.stats.as_dict(),
        "analytic_collectives": analytic_collectives(cfg, geom, shape.kind),
        "geometry": {"n_chunks": geom.n_chunks, "cap": geom.cap,
                     "cap_enc": geom.cap_enc,
                     "enc_stages": geom.enc_stages,
                     "layers_per_stage": geom.layers_per_stage,
                     "policy": geom.policy, "l_ckpt": geom.l_ckpt},
    })
    return rec


def _ctx_specs(cfg, geom, pod, data, model):
    """out_specs for the prefill context buffers: [L_s, ...] per stage =>
    stage dim over "data"; ulysses KV is head-sharded over "model"; the
    allgather_kv buffers and SSM state are replicated over "model"; the conv
    tail is rank-local (per-shard trailing rows).

    Prefill cells always run at d_s_eff == d_s (run_cell pins the planner),
    so the ulysses head dim is evenly sharded over the full model axis —
    sub-degree would leave it replicated within contiguous replica groups,
    which these specs do not express."""
    from jax.sharding import PartitionSpec as P
    from repro.models import LayerCtx
    s = cfg.spec
    k = v = hh = tail = None
    if not s.attn_free:
        if geom.policy == "ulysses":
            k = P(data, None, model, None)
            v = P(data, None, model, None)
        else:
            k = P(data, None, None, None)
            v = P(data, None, None, None)
    if s.ssm_state > 0:
        hh = P(data, None, None)
        # the conv tail is rank-local (each rank's trailing rows); the
        # dry-run output takes one representative — decode resharding
        # recomputes it from the cache anyway.
        tail = P(data, None, None)
    return LayerCtx(k, v, hh, tail)


CELLS = None


def all_cells():
    from repro.configs import SHAPES, arch_names
    cells = []
    for arch in arch_names():
        for shape in SHAPES:
            cells.append((arch, shape))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--remat", type=int, default=None)
    ap.add_argument("--zero3", default="per_tick",
                    choices=["per_tick", "per_step"])
    ap.add_argument("--ckpt-policy", default="stage-aware",
                    choices=["stage-aware", "uniform"],
                    help="per-stage remat axis of the sweep: bake the "
                         "ILP's per-(stage, chunk) vector into each cell "
                         "(stage-aware) or its collapsed max (uniform)")
    ap.add_argument("--sp-policy", default="auto",
                    choices=["auto", "none", "ulysses", "allgather_kv"],
                    help="pin the plan's SP policy (train cells only; "
                         "prefill always runs the full model axis)")
    ap.add_argument("--sp-degree", type=int, default=0,
                    help="pin the effective SP degree (0 = planner-chosen; "
                         "must divide the model-axis size)")
    ap.add_argument("--note", default="")
    ap.add_argument("--cache-dir", default="",
                    help="persistent compile-cache directory shared across "
                         "sweep runs (warm-starts recompiled cells)")
    ap.add_argument("--lint", default="off",
                    choices=["off", "warn", "error"],
                    help="program auditor on each cell's cold compile "
                         "(HLO text tier). Default off: auditing re-"
                         "renders the 256+-device HLO text per cell; "
                         "the offline `python -m repro.lint` CLI audits "
                         "the same programs with full jaxpr visibility")
    args = ap.parse_args()

    if args.lint != "off":
        from repro.launch.mesh import latency_hiding_active
        from repro.lint import make_cache_lint
        _CELL_CACHE.lint = make_cache_lint(
            args.lint, log=print, latency_hiding=latency_hiding_active())
    if args.cache_dir:
        attach_cell_store(args.cache_dir)
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    cells = all_cells() if args.all else [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multipod]

    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}"
            if args.note:
                tag += f"__{args.note}"
            path = out_dir / f"{tag}.json"
            if path.exists():
                print(f"[skip-cached] {tag}")
                continue
            print(f"[run] {tag}", flush=True)
            try:
                rec = run_cell(arch, shape, mp, out_dir,
                               remat_override=args.remat, note=args.note,
                               zero3_mode=args.zero3,
                               ckpt_policy=args.ckpt_policy,
                               sp_policy=args.sp_policy,
                               sp_degree=args.sp_degree)
            except Exception as e:  # noqa: BLE001
                rec = {"arch": arch, "shape": shape,
                       "mesh": "2x16x16" if mp else "16x16",
                       "status": "error", "error": repr(e),
                       "traceback": traceback.format_exc()[-4000:]}
                failures += 1
            # atomic: a concurrent sweep aggregator never reads a torn cell
            from repro.telemetry import atomic_write_json
            atomic_write_json(path, rec)
            print(f"  -> {rec['status']}"
                  + (f" compile={rec.get('compile_s')}s"
                     f" flops={rec.get('flops', 0):.3e}"
                     if rec["status"] == "ok" else
                     f" {rec.get('reason', rec.get('error', ''))[:200]}"),
                  flush=True)
    print(f"[compile-cache] {_CELL_CACHE.stats.summary()}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
