"""End-to-end EPP training driver (the paper's Fig. 4 runtime).

Disaggregated solver/executor: while step i executes on devices, the host
plans batch i+1 (the planner is pure NumPy). Plans are bucketed so compiled
executables are reused; fault tolerance comes from CheckpointManager
(restart) + StragglerMonitor (replanning with per-stage slowdowns).

Runs end-to-end on CPU at reduced scale (examples/quickstart.py) and lowers
unchanged for the production meshes.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b \\
      --reduced --steps 20 --batch 16 --context 2048 --mesh 2x4
"""

from __future__ import annotations

import argparse
import json
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

import numpy as np


class StepMemoryError(RuntimeError):
    """A compiled train step does not fit the device's memory."""


@dataclass
class TrainLoopConfig:
    steps: int = 50
    global_batch: int = 16
    context: int = 2048
    dataset: str = "github"
    seed: int = 0
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 20
    resume: bool = False
    # persistent compile-cache directory. None derives a sibling of
    # ckpt_dir (<ckpt_dir>_compile_cache) when checkpointing is on; ""
    # disables the store (in-memory cache only).
    cache_dir: Optional[str] = None
    # cache-store gc at startup: drop entries not loaded within
    # cache_gc_age_s seconds / shrink the store to cache_gc_bytes payload
    # bytes. None disables the corresponding limit.
    cache_gc_age_s: Optional[float] = None
    cache_gc_bytes: Optional[int] = None
    bucket_rounding: int = 256
    compute_dtype: str = "bfloat16"
    # pipeline schedule backend (core/schedule.py registry name); None lets
    # the planner's bubble model pick on the bootstrap plan. Either way the
    # choice is pinned for the whole run — interleaved stacking bakes the
    # virtual-stage count into the parameter layout.
    schedule: Optional[str] = None
    v_stages: int = 0                 # 0 => auto (interleaved only)
    # remat policy the compiled step applies: "stage-aware" threads the
    # ILP's per-(stage, chunk) checkpoint vector into the executor
    # (encoder/decoder stages and hot/cold chunks remat differently);
    # "uniform" collapses it to one max depth (the pre-vector behavior).
    # Either way the planner raises the depth until the compiled step
    # fits the device (core/planner.py, CostModel.m_step), and a step the
    # TPU compiler still refuses raises it further (StepMemoryError).
    # Parity is guaranteed either way — remat never changes the math
    # (tests/test_remat_parity.py). Tradeoff: the vector is part of the
    # compiled step's identity, so memory-pressured workloads whose solved
    # tables vary step to step fragment the compile cache one bucket per
    # distinct table — pass "uniform" to maximize executable reuse
    # (workloads whose table solves to a constant, incl. the common
    # all-zero case, collapse to the uniform digest automatically).
    ckpt_policy: str = "stage-aware"
    # zero-bubble B/W backward split: "auto" follows the schedule backend
    # (split for zero-bubble-h1, fused otherwise), "on"/"off" force it.
    # Parity is guaranteed either way (tests/test_split_backward.py).
    split_bwd: str = "auto"
    # program auditor (repro.lint): "warn" logs findings from the plan
    # and program passes on every cold compile, "error" aborts before a
    # hazardous executable enters the cache, "off" skips the audit.
    lint: str = "warn"
    # sequence-parallel axis pins forwarded to the planner: "auto" lets
    # the solver choose (policy, d_s_eff) jointly with chunking per plan;
    # a policy name and/or a degree (0 = auto) pins that coordinate. Pins
    # are part of the plan, so they get their own bucket-key / cache-store
    # identity — no cross-SP executable aliasing.
    sp_policy: str = "auto"
    sp_degree: int = 0
    # --- online re-planning (src/repro/telemetry/) ---
    # "off" = static cost model (legacy behavior); "observe" = collect
    # telemetry, fit calibrations and log would-be swaps without touching
    # the plans (numerics provably unchanged); "auto" = close the loop:
    # per-step solves use the active calibration and drift-triggered
    # re-plans hot-swap at step boundaries (hysteresis + cooldown +
    # plan-lint gated, fresh buckets precompiled off-thread).
    replan: str = "off"
    telemetry_dir: Optional[str] = None  # JSONL spill + plan journal +
    #                                      per-mesh calibration persistence
    # every Nth step is a probe: jax.block_until_ready brackets the step
    # so its wall time excludes async dispatch, and a per-stage breakdown
    # is attributed (0 = never probe; EMA counters stay always-on)
    probe_every: int = 0
    replan_min_win: float = 0.05
    replan_cooldown: int = 8
    replan_min_samples: int = 4
    # re-plan jobs on a background thread (the training loop never blocks
    # on the fit/ILP/precompile); False runs them inline — deterministic
    # swap timing for tests
    replan_background: bool = True
    # deterministic telemetry-only straggler injection for tests/CI:
    # "STAGE:FACTOR[,...][@START]", e.g. "2:2.5@3" (ft.StragglerInjector)
    inject_straggler: str = ""
    # two-phase drifting traces: switch the length-mix preset to dataset2
    # at step drift_at (0 = never) — the CI replan job's short-uniform ->
    # long-skewed trace
    dataset2: Optional[str] = None
    context2: int = 0              # 0 = keep --context across the drift
    drift_at: int = 0
    # replay the per-step plans from this JSONL journal instead of solving
    # (written to <telemetry_dir>/plans.jsonl by any telemetry-enabled
    # run): the pinned-plan baseline the CI job compares bitwise against
    plan_journal: Optional[str] = None


def train(cfg_arch, mesh, loop: TrainLoopConfig, *, log=print):
    import jax
    import jax.numpy as jnp

    from repro.ckpt import CheckpointManager
    from repro.core import (ClusterSpec, CostModel, ExecutionPlan,
                            PlannerConfig, plan_batch)
    from repro.data import materialize_plan, sample_corpus_batch
    from repro.ft import (StragglerInjector, StragglerMonitor,
                          replan_costmodel)
    from repro.launch.mesh import enable_compile_cache, latency_hiding_active
    from repro.lint import make_cache_lint, run_plan_checks
    from repro.optim import init_opt_state
    from repro.runtime import (CacheStore, CompileCache, TrainStepBuilder,
                               batch_struct, make_geometry,
                               store_fingerprint)
    from repro.runtime.sharding import mesh_axis_names
    from repro.telemetry import (ReplanConfig, ReplanController,
                                 StepTimeline, read_jsonl)

    enable_compile_cache()
    pod, data, model = mesh_axis_names(mesh)
    n_pods = mesh.shape[pod] if pod else 1
    d_p, d_s = mesh.shape[data], mesh.shape[model]
    dtype = jnp.bfloat16 if loop.compute_dtype == "bfloat16" else jnp.float32

    dev = mesh.devices.flat[0]
    base_cm = CostModel(cfg_arch.spec,
                        ClusterSpec.for_device(dev.platform, dev.device_kind,
                                               d_p=d_p, d_s=d_s,
                                               n_pods=n_pods))
    monitor = StragglerMonitor(d_p=d_p)
    mgr = CheckpointManager(loop.ckpt_dir) if loop.ckpt_dir else None

    # persistent compile cache: warm-start buckets across (elastic)
    # restarts. Entries are fingerprinted by topology + config so a
    # resharded mesh or changed arch falls back to cold compile.
    cache_dir = loop.cache_dir
    if cache_dir is None and loop.ckpt_dir:
        p = Path(loop.ckpt_dir)
        cache_dir = str(p.with_name(p.name + "_compile_cache"))
    store = None
    gc_report = None
    if cache_dir:
        store = CacheStore(cache_dir,
                           store_fingerprint(mesh, spec=cfg_arch.spec,
                                             compute_dtype=dtype),
                           log=log)
        # age/size-budget gc before the run touches the store: stale
        # topologies and cold buckets age out, recently-loaded entries
        # survive (load() refreshes their mtime)
        gc_report = store.gc(max_age_s=loop.cache_gc_age_s,
                             max_bytes=loop.cache_gc_bytes)
    # program auditor: every cold compile is linted once; the build
    # closure stashes the Lowered's StableHLO so the donation pass can see
    # buffer-donor markers the compiled HLO no longer carries
    lint_stash = {}
    lint_hook = make_cache_lint(loop.lint, log=log,
                                latency_hiding=latency_hiding_active(),
                                stash=lint_stash)
    step_cache = CompileCache(name="train-step", log=log, store=store,
                              lint=lint_hook)
    params = opt = None
    start_step = 0

    # --- telemetry: collection + (optionally) the re-planning loop ---
    timeline = StepTimeline(spill_dir=loop.telemetry_dir, name="train")
    injector = (StragglerInjector.parse(loop.inject_straggler, d_p,
                                        seed=loop.seed)
                if loop.inject_straggler else None)
    # plan journal: every telemetry-enabled run records the plan it
    # EXECUTED each step; a journal replay run re-executes exactly those
    # plans (--plan-journal), which is how CI proves the control plane is
    # numerically non-intrusive (bitwise-equal losses)
    journal_out = None
    if loop.telemetry_dir:
        jp = Path(loop.telemetry_dir) / "plans.jsonl"
        jp.parent.mkdir(parents=True, exist_ok=True)
        journal_out = open(jp, "w", buffering=1)
    journal_in = {}
    if loop.plan_journal:
        for rec in read_jsonl(loop.plan_journal):
            journal_in[int(rec["step"])] = ExecutionPlan.loads(rec["plan"])
        if not journal_in:
            raise ValueError(f"--plan-journal {loop.plan_journal} holds no "
                             f"replayable plans")
        log(f"[journal] replaying {len(journal_in)} plans from "
            f"{loop.plan_journal}")

    # schedule backend is pinned after the bootstrap plan: interleaved
    # stacking bakes v_stages into the parameter layout, so mid-run
    # schedule switches would scramble live training state
    pinned = {"schedule": loop.schedule, "v_stages": loop.v_stages}
    remat_mode = ("stage_aware" if loop.ckpt_policy == "stage-aware"
                  else "uniform")
    # least remat depth: raised when the compiler refuses a step
    ckpt_floor = {"depth": 0}
    dev_limit = ((dev.memory_stats() or {}).get("bytes_limit")
                 or base_cm.cluster.hbm_bytes)

    def solve(cm, lengths):
        plan = plan_batch(cm, lengths,
                          PlannerConfig(bucket_rounding=loop.bucket_rounding,
                                        schedule=pinned["schedule"],
                                        v_stages=pinned["v_stages"],
                                        remat_mode=remat_mode,
                                        ckpt_floor=ckpt_floor["depth"],
                                        sp_policy=loop.sp_policy,
                                        sp_degree=loop.sp_degree))
        pinned["schedule"], pinned["v_stages"] = plan.schedule, plan.v_stages
        return plan

    def bucket_of(plan):
        return str(plan.bucket_key(d_s, split_bwd=loop.split_bwd,
                                   dtype=loop.compute_dtype))

    def plan_lint(plan):
        """Plan-invariant errors for a re-plan candidate: a hazardous
        re-planned program is rejected BEFORE the swap."""
        if loop.lint == "off":
            return []
        prep = run_plan_checks(plan, d_s, d_p, model=cfg_arch.spec,
                               key_kwargs={"split_bwd": loop.split_bwd,
                                           "dtype": loop.compute_dtype})
        return [str(e) for e in prep.errors]

    def resolve_incumbent(cm, lengths, inc):
        """The hysteresis strawman: this batch re-planned under the
        incumbent's bucket — capacity AND sp policy pinned, else the
        "held" solve silently makes the candidate's own move and the
        comparison degenerates. Does not touch ``pinned`` (it is a
        what-if, never executed)."""
        key = inc.bucket_key(d_s, split_bwd=loop.split_bwd,
                             dtype=loop.compute_dtype)
        return plan_batch(cm, lengths,
                          PlannerConfig(bucket_rounding=loop.bucket_rounding,
                                        schedule=pinned["schedule"],
                                        v_stages=pinned["v_stages"],
                                        remat_mode=remat_mode,
                                        ckpt_floor=ckpt_floor["depth"],
                                        sp_policy=key.sp_policy,
                                        sp_degree=key.d_s_eff,
                                        token_capacity=key.cap))

    controller = None
    if loop.replan in ("observe", "auto") and not journal_in:
        controller = ReplanController(
            base_cm,
            ReplanConfig(mode=loop.replan, min_win=loop.replan_min_win,
                         cooldown_steps=loop.replan_cooldown,
                         min_samples=loop.replan_min_samples,
                         background=loop.replan_background),
            solve, bucket_of, lint=plan_lint,
            resolve_incumbent=resolve_incumbent,
            precompile=lambda p: get_step(p),
            timeline=timeline, telemetry_dir=loop.telemetry_dir,
            fingerprint=(f"{d_p}x{d_s}:{cfg_arch.spec.name}"),
            log=log)

    def mix_for(step: int):
        if loop.drift_at and step >= loop.drift_at and (
                loop.dataset2 or loop.context2):
            return (loop.dataset2 or loop.dataset,
                    loop.context2 or loop.context)
        return loop.dataset, loop.context

    def plan_for(step: int):
        ds, ctx = mix_for(step)
        corpus = sample_corpus_batch(ds, loop.global_batch,
                                     ctx, cfg_arch.spec.vocab,
                                     seed=loop.seed + step)
        if journal_in:
            # replay: past the journal's end (the final overlap solve) the
            # last journaled plan stands in — it is never executed
            plan = journal_in.get(step) or journal_in[max(journal_in)]
            pinned["schedule"], pinned["v_stages"] = (plan.schedule,
                                                      plan.v_stages)
            return plan, corpus
        cm = (controller.cost_model() if controller is not None
              else base_cm)
        cm = replan_costmodel(cm, monitor)
        lengths = [len(v) for v in corpus.values()]
        return solve(cm, lengths), corpus

    def get_step(plan):
        # split_bwd and dtype are key fields now (plan-bucket-key lint
        # proves every axis that changes the lowering changes the key), so
        # a forced B/W split no longer needs an out-of-band cache identity
        key = plan.bucket_key(d_s, split_bwd=loop.split_bwd,
                              dtype=loop.compute_dtype)
        # the builder is cheap host-side state (geometry + specs); only
        # the compiled executable is cached — and, via the store, persisted.
        # ckpt_policy() canonicalizes the remat vector (padded to the
        # bucket's chunk count; constant tables collapse to the uniform
        # scalar) — the same canonical form key.ckpt digests, so the cache
        # can never hand this geometry a wrong-remat executable.
        l_max, table, _digest = plan.ckpt_policy(key.n_chunks)
        split = (None if loop.split_bwd == "auto"
                 else loop.split_bwd == "on")
        # the SP axis rides the bucket key (legacy sp-less plans resolve
        # to policy "auto" / full degree there, which make_geometry maps
        # back to the old rederive-at-full-d_s behavior)
        geom = make_geometry(cfg_arch, mesh, n_chunks=key.n_chunks,
                             cap=key.cap, ctx_cap=key.ctx_cap,
                             l_ckpt=l_max, compute_dtype=dtype,
                             schedule=key.schedule, v_stages=key.v_stages,
                             ckpt_table=table, split_bwd=split,
                             sp_policy=(None if key.sp_policy == "auto"
                                        else key.sp_policy),
                             sp_degree=key.d_s_eff)
        builder = TrainStepBuilder(cfg_arch, mesh, geom, param_dtype=dtype)

        def build():
            # plan invariants are audited before the (expensive) compile:
            # a schedule whose ticks don't cover every (item, v) slot or a
            # ckpt table that disagrees with the geometry never lowers
            if lint_hook is not None:
                prep = run_plan_checks(
                    plan, d_s, d_p, model=cfg_arch.spec,
                    key_kwargs={"split_bwd": loop.split_bwd,
                                "dtype": loop.compute_dtype})
                for f in prep.findings:
                    log(f"[lint] {f}")
                step_cache.stats.lint_findings += len(prep.findings)
                step_cache.stats.lint_errors += len(prep.errors)
                if prep.findings:
                    timeline.record("lint", bucket=str(key),
                                    findings=len(prep.findings),
                                    errors=len(prep.errors))
                if loop.lint == "error":
                    prep.raise_if_findings()
            # AOT lower+compile against abstract shapes: the resulting
            # jax.stages.Compiled is what serialize_executable can persist
            params_shape = builder.abstract_params()
            opt_shape = jax.eval_shape(init_opt_state, params_shape)
            bstruct = batch_struct(geom, n_pods)
            lowered = builder.build(params_shape).lower(
                params_shape, opt_shape, None, bstruct)
            if lint_hook is not None:
                lint_stash["stablehlo"] = lowered.as_text()
            want = plan.meta.get("step_bytes", 0.0)
            try:
                compiled = lowered.compile()
            except Exception as e:  # noqa: BLE001 — XlaRuntimeError
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                raise StepMemoryError(
                    f"bucket {key}: the compiler refuses the step "
                    f"(planner estimate {want / 1e9:.2f} GB): "
                    f"{str(e).splitlines()[0]}") from e
            ma = compiled.memory_analysis()
            # the CPU backend reports no peak: bound it by the buffers
            peak = ma.peak_memory_in_bytes or (
                ma.temp_size_in_bytes + ma.argument_size_in_bytes
                + ma.output_size_in_bytes - ma.alias_size_in_bytes)
            log(f"[memory] bucket {key}: planner estimate "
                f"{want / 1e9:.2f} GB, compiled peak {peak / 1e9:.2f} GB "
                f"of {dev_limit / 1e9:.2f} GB")
            if peak > dev_limit:
                raise StepMemoryError(
                    f"bucket {key}: compiled peak {peak / 1e9:.2f} GB "
                    f"exceeds the device's {dev_limit / 1e9:.2f} GB")
            return compiled
        m0, w0 = step_cache.stats.misses, step_cache.stats.warm_hits
        compiled = step_cache.get(key, build)
        if step_cache.stats.misses > m0:
            timeline.record("compile", bucket=str(key), event="cold",
                            compile_s=step_cache.stats
                            .compile_seconds_per_key.get(repr(key), 0.0))
        elif step_cache.stats.warm_hits > w0:
            timeline.record("compile", bucket=str(key), event="warm")
        return builder, compiled

    def fitted_step(step, plan, corpus):
        """``get_step(plan)``; while the step does not fit the device,
        raise the remat floor past the plan's depth and re-plan."""
        while True:
            try:
                return plan, corpus, get_step(plan)
            except StepMemoryError as e:
                key = plan.bucket_key(d_s)
                depth = key.l_ckpt if key.ckpt.startswith("u") else 0
                if journal_in or depth >= -(-cfg_arch.spec.n_layers // d_p):
                    raise
                ckpt_floor["depth"] = depth + 1
                log(f"[memory] {e}; re-planning with remat depth >= "
                    f"{ckpt_floor['depth']}")
                plan, corpus = plan_for(step)

    # --- bootstrap: plan step 0 to learn the first bucket ---
    plan, corpus = plan_for(0)
    log(f"[schedule] {plan.schedule} v={plan.v_stages} "
        f"(pinned for this run)")
    if plan.sp is not None:
        log(f"[sp] policy={plan.sp.policy} d_s_eff={plan.sp.d_s_eff}/{d_s}"
            + (" (planner-chosen)" if loop.sp_policy == "auto"
               and not loop.sp_degree else " (pinned)"))
    _key0 = plan.bucket_key(d_s)
    log(f"[ckpt] policy={loop.ckpt_policy} digest={_key0.ckpt} "
        f"l_max={_key0.l_ckpt}"
        + ("" if _key0.ckpt.startswith("u") else
           f" per_stage_max={plan.ckpt_per_stage_max()}"))
    plan, corpus, (builder, step_fn) = fitted_step(0, plan, corpus)
    params, opt, _ = builder.init_all(jax.random.PRNGKey(loop.seed))
    def _restack(saved: np.ndarray, tmpl) -> Optional[np.ndarray]:
        """Elastic reshard: stage-stacked [d_p_old, L_s_old, ...] leaves
        restack for the current pipeline depth (un-permute the interleaved
        placement with the run's pinned v, strip old padding, re-pad).
        The v_stages guard after restore rejects checkpoints written at a
        different v, so assuming the pinned v here is sound."""
        if saved.ndim != len(tmpl.shape) or saved.ndim < 2 \
                or tuple(saved.shape[2:]) != tuple(tmpl.shape[2:]):
            return None
        from repro.runtime.sharding import restack_elastic
        return restack_elastic(saved, tmpl.shape[0], tmpl.shape[1],
                               cfg_arch.spec.n_layers, v=plan.v_stages)

    if mgr and loop.resume:
        latest = mgr.latest_step()
        if latest is not None:
            (params, opt), extra = mgr.restore((params, opt),
                                               adapt=_restack)
            # interleaved stacking permutes layers WITHOUT changing leaf
            # shapes, so a v_stages mismatch cannot be shape-detected —
            # loading it silently would scramble layers across virtual
            # stages. Checkpoints from before the schedule field are v=1.
            saved_v = int(extra.get("v_stages", 1))
            if saved_v != plan.v_stages:
                raise ValueError(
                    f"checkpoint was written with v_stages={saved_v} but "
                    f"this run pinned {plan.schedule} v={plan.v_stages}; "
                    f"pass --schedule/--v-stages matching the checkpoint "
                    f"(layer stacking is v-dependent and cannot be "
                    f"restacked across v)")
            start_step = int(extra.get("step", latest)) + 1
            log(f"[resume] from step {start_step - 1}")

    def mat(plan, corpus, cap, n_chunks):
        cb = materialize_plan(plan, corpus)
        b = {k: np.asarray(v) for k, v in cb.as_dict().items()}
        b["tokens"] = np.where(b["seg"] >= 0, b["tokens"], 0)
        b["pos"] = np.where(b["seg"] >= 0, b["pos"], 0)
        pad = cap - b["tokens"].shape[1]
        if pad > 0:
            for k, fill in (("tokens", 0), ("targets", -1), ("seg", -1),
                            ("pos", 0)):
                b[k] = np.pad(b[k], ((0, 0), (0, pad)),
                              constant_values=fill)
        padc = n_chunks - b["tokens"].shape[0]
        if padc > 0:  # bucket padding: fully-masked empty chunks
            for k, fill in (("tokens", 0), ("targets", -1), ("seg", -1),
                            ("pos", 0)):
                b[k] = np.pad(b[k], ((0, padc), (0, 0)),
                              constant_values=fill)
            b["ctx_len"] = np.pad(b["ctx_len"], (0, padc))
        if n_pods > 1:
            b = {k: v.reshape(n_pods, v.shape[0] // n_pods, *v.shape[1:])
                 for k, v in b.items()}
        return {k: jnp.asarray(v) for k, v in b.items()}

    history = []
    next_plan, next_corpus = plan, corpus
    for step in range(start_step, loop.steps):
        plan, corpus, (builder, step_fn) = fitted_step(step, next_plan,
                                                       next_corpus)
        if journal_out is not None:
            journal_out.write(json.dumps({"step": step,
                                          "plan": plan.dumps()}) + "\n")
        key = plan.bucket_key(d_s)
        bucket = bucket_of(plan)
        batch = mat(plan, corpus, key.cap, key.n_chunks)
        probed = bool(loop.probe_every) and step % loop.probe_every == 0
        t0 = time.perf_counter()
        params, opt, _err, metrics = step_fn(params, opt, None, batch)
        dt_probe = None
        if probed:
            # probe mode: block_until_ready brackets the device step so
            # the sample excludes async dispatch (and the overlapped
            # solver below); per-stage attribution divides it across the
            # pipeline — the injector can then skew individual stages
            jax.block_until_ready(metrics["loss"])
            dt_probe = time.perf_counter() - t0
        # overlap: next iteration's plan solves while devices run
        next_plan, next_corpus = plan_for(step + 1)
        loss = float(metrics["loss"])
        dt_step = time.perf_counter() - t0
        wall = dt_probe if dt_probe is not None else dt_step
        per_stage = None
        wall_rep = wall
        if probed:
            per_stage = [wall / d_p] * d_p
            if injector is not None:
                per_stage = injector.per_stage(per_stage, step)
        if injector is not None:
            wall_rep = injector.wall(wall, step)
        timeline.record_step(step, bucket, wall_rep,
                             tokens=float(metrics["tokens"]), loss=loss,
                             per_stage_s=per_stage, probed=probed)
        history.append({"step": step, "loss": loss, "time": dt_step,
                        "wall": wall,
                        "tokens": float(metrics["tokens"]),
                        "slots": key.n_chunks * key.cap,
                        "solve_time": plan.solve_time})
        log(f"step {step:5d} loss {loss:.4f} tokens "
            f"{int(metrics['tokens'])} wall {dt_step:.2f}s "
            f"(solver {plan.solve_time:.2f}s overlapped)")
        if controller is not None:
            lengths = [len(v) for v in corpus.values()]
            controller.observe_step(step, plan, wall_rep, lengths,
                                    per_stage_s=per_stage, bucket=bucket)
            swap = controller.poll()
            if swap is not None and loop.replan == "auto":
                # hot-swap at the step boundary: the overlapped solve
                # above used the pre-swap calibration, so re-solve the
                # next step under the newly adopted one (a previously-seen
                # bucket is a warm hit; a fresh one was precompiled by the
                # re-plan job before adoption)
                next_plan, next_corpus = plan_for(step + 1)
        if mgr and (step + 1) % loop.ckpt_every == 0:
            mgr.save(step, (params, opt),
                     extra={"step": step, "schedule": plan.schedule,
                            "v_stages": plan.v_stages})
    if mgr:
        mgr.wait()
    if controller is not None:
        controller.drain()
        controller.poll()  # account a job that outlived the loop
        log(f"[replan] version={controller.version} "
            f"counters={controller.counters} "
            f"triggers={controller.trigger_reasons}")
    if journal_out is not None:
        journal_out.close()
    log(f"[compile-cache] {step_cache.stats.summary()}")
    rep = store.report() if store is not None else None
    if rep is not None:
        log(f"[cache-store] dir={rep['dir']} entries={rep['entries']} "
            f"({rep['size_bytes'] / 1e6:.2f} MB) saves={rep['saves']} "
            f"warm_loads={rep['loads']} stale={rep['stale_skips']} "
            f"corrupt={rep['corrupt_skips']}")
    if history:
        history[-1]["compile_cache"] = step_cache.stats.as_dict()
        if rep is not None:
            history[-1]["cache_store"] = rep
            history[-1]["cache_store_gc"] = gc_report
        history[-1]["telemetry"] = timeline.snapshot()
        if controller is not None:
            history[-1]["replan"] = controller.snapshot()
    timeline.close()
    return params, opt, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--context", type=int, default=2048)
    ap.add_argument("--dataset", default="github")
    ap.add_argument("--mesh", default=None,
                    help="DPxSP, e.g. 2x4; default: 2 pipeline stages "
                         "(1 on one device) over all devices")
    ap.add_argument("--devices", type=int, default=0,
                    help="virtual CPU devices (CPU backend only; default: "
                         "the --mesh size)")
    ap.add_argument("--ckpt-dir")
    ap.add_argument("--cache-dir", default=None,
                    help="persistent compile-cache directory (warm-starts "
                         "plan buckets across restarts); default: "
                         "<ckpt-dir>_compile_cache when --ckpt-dir is set, "
                         "'' disables")
    ap.add_argument("--cache-gc-age-s", type=float, default=0.0,
                    help="cache-store gc at startup: drop entries not "
                         "loaded in this many seconds (0 = off)")
    ap.add_argument("--cache-gc-bytes", type=int, default=0,
                    help="cache-store gc at startup: shrink the store to "
                         "this many payload bytes (0 = off)")
    ap.add_argument("--stats-json", default="",
                    help="write the run history + compile-cache/store "
                         "stats to this JSON file (CI artifact)")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--schedule", default=None,
                    help="pipeline schedule backend (gpipe-1f1b, "
                         "interleaved-1f1b, zero-bubble-h1); default: "
                         "planner's bubble model picks")
    ap.add_argument("--v-stages", type=int, default=0,
                    help="virtual stages per device for interleaved-1f1b "
                         "(0 = auto; must divide layers per stage)")
    ap.add_argument("--ckpt-policy", default="stage-aware",
                    choices=["stage-aware", "uniform"],
                    help="remat policy baked into the compiled step: "
                         "'stage-aware' threads the ILP's per-(stage, "
                         "chunk) checkpoint vector into the executor; "
                         "'uniform' collapses it to one max depth")
    ap.add_argument("--split-bwd", default="auto",
                    choices=["auto", "on", "off"],
                    help="zero-bubble B/W backward split: 'auto' follows "
                         "the schedule backend (split for zero-bubble-h1), "
                         "'on'/'off' force it for any backend (parity is "
                         "guaranteed either way)")
    ap.add_argument("--lint", default="warn",
                    choices=["off", "warn", "error"],
                    help="program auditor on cold compiles: 'warn' logs "
                         "findings (and counts them in --stats-json), "
                         "'error' aborts before a hazardous executable "
                         "enters the compile cache, 'off' skips the audit")
    ap.add_argument("--sp-policy", default="auto",
                    choices=["auto", "none", "ulysses", "allgather_kv"],
                    help="sequence-parallel policy pin: 'auto' lets the "
                         "planner choose (policy, degree) jointly with "
                         "chunking; a name pins the policy (the pin gets "
                         "its own plan bucket / compile-cache identity)")
    ap.add_argument("--sp-degree", type=int, default=0,
                    help="effective SP degree pin (sub-groups of the "
                         "model axis; must divide the mesh's SP size); "
                         "0 = planner-chosen")
    ap.add_argument("--replan", default="off",
                    choices=["off", "observe", "auto"],
                    help="online re-planning: 'observe' collects telemetry "
                         "and logs would-be swaps without touching plans; "
                         "'auto' closes the loop — calibrated per-step "
                         "solves + drift-triggered hysteresis-gated plan "
                         "hot-swaps at step boundaries")
    ap.add_argument("--telemetry-dir", default="",
                    help="directory for the timeline JSONL spill, the "
                         "per-step plan journal (plans.jsonl) and the "
                         "per-mesh calibration store (calibration.json)")
    ap.add_argument("--probe-every", type=int, default=0,
                    help="bracket every Nth step with "
                         "jax.block_until_ready and record a per-stage "
                         "breakdown (0 = never; EMA counters stay on)")
    ap.add_argument("--replan-min-win", type=float, default=0.05,
                    help="hysteresis: a bucket-changing swap needs at "
                         "least this predicted relative win")
    ap.add_argument("--replan-cooldown", type=int, default=8,
                    help="minimum steps between re-plan jobs")
    ap.add_argument("--replan-min-samples", type=int, default=4,
                    help="telemetry samples before the first fit")
    ap.add_argument("--replan-sync", action="store_true",
                    help="run re-plan jobs inline instead of on the "
                         "background thread (deterministic swap timing)")
    ap.add_argument("--inject-straggler", default="",
                    help="deterministic telemetry-only straggler "
                         "injection 'STAGE:FACTOR[,...][@START]' (e.g. "
                         "'2:2.5@3'); perturbs measurements, never math")
    ap.add_argument("--dataset2", default="",
                    help="switch the length-mix preset to this at "
                         "--drift-at (two-phase drifting traces)")
    ap.add_argument("--context2", type=int, default=0,
                    help="context limit for the post-drift phase "
                         "(0 = keep --context)")
    ap.add_argument("--drift-at", type=int, default=0,
                    help="step at which --dataset2/--context2 take over "
                         "(0 = never)")
    ap.add_argument("--plan-journal", default="",
                    help="replay per-step plans from this plans.jsonl "
                         "instead of solving (pinned-plan baseline)")
    args = ap.parse_args()

    import os
    mesh_shape = (tuple(int(x) for x in args.mesh.split("x"))
                  if args.mesh else None)
    n_virtual = args.devices or (mesh_shape[0] * mesh_shape[1]
                                 if mesh_shape else 0)
    # the forced host-device count sizes the CPU backend only; an
    # accelerator backend meshes its real devices
    flags = os.environ.get("XLA_FLAGS", "")
    if n_virtual and "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_virtual}"
        ).strip()
    import jax

    from repro.configs import get_arch
    from repro.launch.mesh import make_mesh
    if args.devices and jax.default_backend() != "cpu":
        print(f"[mesh] --devices ignored on {jax.default_backend()}")
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if mesh_shape is None:
        n = jax.device_count()
        dp = 2 if n > 1 and n % 2 == 0 else 1
        mesh_shape = (dp, n // dp)
    mesh = make_mesh(mesh_shape, ("data", "model"))
    print(f"[mesh] {dict(mesh.shape)} on {jax.device_count()} "
          f"{jax.devices()[0].device_kind} device(s)")
    loop = TrainLoopConfig(steps=args.steps, global_batch=args.batch,
                           context=args.context, dataset=args.dataset,
                           ckpt_dir=args.ckpt_dir, resume=args.resume,
                           cache_dir=args.cache_dir,
                           cache_gc_age_s=args.cache_gc_age_s or None,
                           cache_gc_bytes=args.cache_gc_bytes or None,
                           compute_dtype="float32" if args.reduced
                           else "bfloat16",
                           schedule=args.schedule, v_stages=args.v_stages,
                           ckpt_policy=args.ckpt_policy,
                           split_bwd=args.split_bwd,
                           lint=args.lint,
                           sp_policy=args.sp_policy,
                           sp_degree=args.sp_degree,
                           replan=args.replan,
                           telemetry_dir=args.telemetry_dir or None,
                           probe_every=args.probe_every,
                           replan_min_win=args.replan_min_win,
                           replan_cooldown=args.replan_cooldown,
                           replan_min_samples=args.replan_min_samples,
                           replan_background=not args.replan_sync,
                           inject_straggler=args.inject_straggler,
                           dataset2=args.dataset2 or None,
                           context2=args.context2,
                           drift_at=args.drift_at,
                           plan_journal=args.plan_journal or None)
    _, _, history = train(cfg, mesh, loop)
    if args.stats_json:
        from repro.telemetry import atomic_write_json
        last = history[-1] if history else {}
        atomic_write_json(args.stats_json,
                          {"history": history,
                           "compile_cache": last.get("compile_cache", {}),
                           "cache_store": last.get("cache_store", {}),
                           "cache_store_gc": last.get("cache_store_gc"),
                           "telemetry": last.get("telemetry", {}),
                           "replan": last.get("replan", {})})


if __name__ == "__main__":
    main()
