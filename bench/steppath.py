"""The program's training step path, driven the way ``launch/train.train()``
drives it: ``plan_batch`` -> ``ExecutionPlan.bucket_key`` ->
``make_geometry`` -> ``TrainStepBuilder`` -> the compiled step (through the
plan-bucket ``CompileCache`` and its ``CacheStore``), and the materialized
chunk buffers as the step's feed. Every argument is the one ``train()``
gives with its defaults (bf16, ``stage-aware`` remat, bucket rounding 256,
schedule and SP chosen by the planner and pinned after the first plan);
``bench/tests/test_bench_parity.py`` holds the two to the same loss.

The one departure: ``train()`` audits each cold compile with the program
linter, which reads the plan and the lowered program and changes nothing
in it; the benchmark leaves the linter off.
"""

from __future__ import annotations

from pathlib import Path
from typing import Dict, List, Optional

import numpy as np


def adamw_matches(hp: Dict) -> List[str]:
    """Where the program's AdamW defaults differ from the configuration."""
    from repro.optim import AdamWConfig
    a = AdamWConfig()
    pairs = {"lr": a.lr, "beta1": a.beta1, "beta2": a.beta2, "eps": a.eps,
             "weight_decay": a.weight_decay, "grad_clip": a.grad_clip,
             "warmup_steps": a.warmup_steps, "total_steps": a.total_steps,
             "min_lr_frac": a.min_lr_frac}
    return [f"{k}: program {v} vs configuration {hp[k]}"
            for k, v in pairs.items() if v != hp[k]]


class StepPath:
    """Planner, bucket cache and compiled steps for one mesh."""

    BUCKET_ROUNDING = 256     # train()'s TrainLoopConfig default

    def __init__(self, arch, mesh, store_dir: Optional[str], log=print):
        import jax.numpy as jnp

        from repro.core import ClusterSpec, CostModel
        from repro.runtime import CacheStore, CompileCache, store_fingerprint
        from repro.runtime.sharding import mesh_axis_names
        self.arch, self.mesh, self.log = arch, mesh, log
        pod, data, model = mesh_axis_names(mesh)
        self.n_pods = mesh.shape[pod] if pod else 1
        self.d_p, self.d_s = mesh.shape[data], mesh.shape[model]
        self.dtype = jnp.bfloat16
        dev = mesh.devices.flat[0]
        self.cm = CostModel(arch.spec, ClusterSpec.for_device(
            dev.platform, dev.device_kind, d_p=self.d_p, d_s=self.d_s,
            n_pods=self.n_pods))
        self.dev_limit = ((dev.memory_stats() or {}).get("bytes_limit")
                          or self.cm.cluster.hbm_bytes)
        store = None
        if store_dir:
            store = CacheStore(store_dir, store_fingerprint(
                mesh, spec=arch.spec, compute_dtype=self.dtype), log=log)
        self.cache = CompileCache(name="train-step", log=log, store=store)
        self.pinned = {"schedule": None, "v_stages": 0}
        self.peaks: Dict[str, int] = {}

    # -- planning ----------------------------------------------------------
    def solve(self, lengths):
        from repro.core import PlannerConfig, plan_batch
        plan = plan_batch(self.cm, lengths, PlannerConfig(
            bucket_rounding=self.BUCKET_ROUNDING,
            schedule=self.pinned["schedule"],
            v_stages=self.pinned["v_stages"], remat_mode="stage_aware",
            ckpt_floor=0, sp_policy="auto", sp_degree=0))
        self.pinned["schedule"] = plan.schedule
        self.pinned["v_stages"] = plan.v_stages
        return plan

    def key(self, plan):
        return plan.bucket_key(self.d_s, split_bwd="auto", dtype="bfloat16")

    # -- compiled steps ----------------------------------------------------
    def builder(self, plan):
        from repro.runtime import TrainStepBuilder, make_geometry
        key = self.key(plan)
        l_max, table, _ = plan.ckpt_policy(key.n_chunks)
        geom = make_geometry(
            self.arch, self.mesh, n_chunks=key.n_chunks, cap=key.cap,
            ctx_cap=key.ctx_cap, l_ckpt=l_max, compute_dtype=self.dtype,
            schedule=key.schedule, v_stages=key.v_stages, ckpt_table=table,
            split_bwd=None,
            sp_policy=None if key.sp_policy == "auto" else key.sp_policy,
            sp_degree=key.d_s_eff)
        return TrainStepBuilder(self.arch, self.mesh, geom,
                                param_dtype=self.dtype)

    def get_step(self, plan):
        """(builder, compiled step) of the plan's bucket: resident, loaded
        from the store, or compiled."""
        import jax

        from repro.optim import init_opt_state
        from repro.runtime import batch_struct
        key = self.key(plan)
        builder = self.builder(plan)

        def build():
            params_shape = builder.abstract_params()
            opt_shape = jax.eval_shape(init_opt_state, params_shape)
            lowered = builder.build(params_shape).lower(
                params_shape, opt_shape, None,
                batch_struct(builder.geom, self.n_pods))
            compiled = lowered.compile()
            peak = _peak(compiled)
            if peak > self.dev_limit:
                raise RuntimeError(
                    f"bucket {key}: compiled peak {peak / 1e9:.2f} GB "
                    f"exceeds the device's {self.dev_limit / 1e9:.2f} GB")
            return compiled

        compiled = self.cache.get(key, build)
        if str(key) not in self.peaks:
            self.peaks[str(key)] = _peak(compiled)
        return builder, compiled

    def misses(self) -> int:
        """Lookups that were not resident: store loads and compiles."""
        s = self.cache.stats
        return s.misses + s.warm_hits

    # -- feed ----------------------------------------------------------------
    def materialize(self, plan, corpus: Dict[int, np.ndarray]):
        """The step's chunk buffers, padded to the bucket (train()'s
        ``mat``), as device arrays."""
        import jax.numpy as jnp

        from repro.data import materialize_plan
        key = self.key(plan)
        cb = materialize_plan(plan, corpus)
        b = {k: np.asarray(v) for k, v in cb.as_dict().items()}
        b["tokens"] = np.where(b["seg"] >= 0, b["tokens"], 0)
        b["pos"] = np.where(b["seg"] >= 0, b["pos"], 0)
        pad = key.cap - b["tokens"].shape[1]
        if pad > 0:
            for k, fill in (("tokens", 0), ("targets", -1), ("seg", -1),
                            ("pos", 0)):
                b[k] = np.pad(b[k], ((0, 0), (0, pad)),
                              constant_values=fill)
        padc = key.n_chunks - b["tokens"].shape[0]
        if padc > 0:
            for k, fill in (("tokens", 0), ("targets", -1), ("seg", -1),
                            ("pos", 0)):
                b[k] = np.pad(b[k], ((0, padc), (0, 0)),
                              constant_values=fill)
            b["ctx_len"] = np.pad(b["ctx_len"], (0, padc))
        if self.n_pods > 1:
            b = {k: v.reshape(self.n_pods, v.shape[0] // self.n_pods,
                              *v.shape[1:]) for k, v in b.items()}
        return {k: jnp.asarray(v) for k, v in b.items()}


def _peak(compiled) -> int:
    ma = compiled.memory_analysis()
    return int(ma.peak_memory_in_bytes or (
        ma.temp_size_in_bytes + ma.argument_size_in_bytes
        + ma.output_size_in_bytes - ma.alias_size_in_bytes))


def store_dir(root: Path) -> str:
    return str(root / "bench" / ".cache" / "executables")

