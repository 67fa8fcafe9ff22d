"""Builds the jit'd EPP training step for a mesh + plan-bucket geometry.

Pieces assembled here:

* parameter preparation: model-zoo init -> executor layout (stage-stacked
  layers, vocab padded to d_s, ZeRO/EP/stage PartitionSpecs);
* the shard_map'd step: pipeline loss (runtime/pipeline.py) -> autodiff ->
  head-param grad psum over stages -> pod gradient all-reduce (optionally
  int8-compressed with error feedback) -> ZeRO AdamW on local shards;
* batch specs for the chunk buffers the planner materializes.

Everything static (geometry, remat, policies) is baked per bucket; the
returned step is reused across iterations of the same bucket (§2.3).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models import DecoderLM
from repro.models.config import ArchConfig
from repro.optim import (AdamWConfig, adamw_update, compressed_psum,
                         init_error_state, init_opt_state)

from . import sp
from .pipeline import PipelineGeometry, pipeline_loss_fn
from .sharding import (batch_specs, mesh_axis_names, shard_dim_tree,
                       stack_stages, stage_param_specs)

__all__ = ["TrainStepBuilder", "prepare_params", "make_geometry",
           "batch_struct"]


def _pad_vocab(w: jnp.ndarray, d_s: int) -> jnp.ndarray:
    pad = (-w.shape[0]) % d_s
    if pad:
        w = jnp.concatenate([w, jnp.zeros((pad, *w.shape[1:]), w.dtype)])
    return w


def make_geometry(cfg: ArchConfig, mesh: Mesh, *, n_chunks: int, cap: int,
                  ctx_cap: int, l_ckpt: int = 0,
                  compute_dtype=jnp.bfloat16,
                  zero3_mode: str = "per_tick",
                  schedule: str = "gpipe-1f1b",
                  v_stages: int = 1,
                  ckpt_table=None,
                  split_bwd: Optional[bool] = None,
                  overlap_handoff: bool = True,
                  sp_policy: Optional[str] = None,
                  sp_degree: int = 0) -> PipelineGeometry:
    """``ckpt_table`` (optional): the solver's per-(stage, chunk) remat
    matrix — any (d_p, n_chunks) nested sequence; canonicalized to the
    hashable tuple-of-tuples the frozen geometry stores. None keeps the
    uniform ``l_ckpt`` policy.

    ``split_bwd`` (optional): force the zero-bubble B/W backward split on
    or off; None defaults to the schedule backend's capability
    (``ScheduleSpec.split_bwd`` — i.e. on for ``zero-bubble-h1``).

    ``sp_policy``/``sp_degree`` (optional): the plan's SP axis
    (``ExecutionPlan.sp`` / ``bucket_key().sp_policy/d_s_eff``). Defaults
    — policy None, degree 0 — resolve to the core heuristic at the full
    model-axis size, which is the legacy sp-less-plan behavior."""
    from .executor import canonical_ckpt_table
    from repro.core.schedule import get_schedule
    pod, data, model = mesh_axis_names(mesh)
    d_p = mesh.shape[data]
    d_s = mesh.shape[model]
    d_s_eff = sp_degree or d_s
    ckpt_table = canonical_ckpt_table(ckpt_table, d_p=d_p,
                                      n_chunks=n_chunks)
    if split_bwd is None:
        split_bwd = get_schedule(schedule, v_stages).split_bwd
    return PipelineGeometry(
        n_chunks=n_chunks, cap=cap, ctx_cap=ctx_cap, d_p=d_p, d_s=d_s,
        l_ckpt=l_ckpt,
        layers_per_stage=-(-cfg.spec.n_layers // d_p),
        policy=sp_policy or sp.choose_policy(cfg, d_s_eff),
        d_s_eff=d_s_eff,
        compute_dtype=compute_dtype,
        zero3_mode=zero3_mode,
        schedule=schedule,
        v_stages=v_stages,
        ckpt_table=ckpt_table,
        split_bwd=split_bwd,
        overlap_handoff=overlap_handoff)


def prepare_params(cfg: ArchConfig, raw_params: Dict, mesh: Mesh,
                   param_dtype=jnp.bfloat16, v_stages: int = 1) -> Dict:
    """Model-zoo params -> executor layout (host-side, un-sharded arrays).

    ``v_stages > 1`` bakes the interleaved-1f1b virtual-stage placement
    into the stage stacking (sharding.interleaved_layer_order) — the layout
    is schedule-shaped, which is why the schedule leads
    ``ExecutionPlan.bucket_key()`` and is pinned per training run."""
    pod, data, model = mesh_axis_names(mesh)
    d_p, d_s = mesh.shape[data], mesh.shape[model]
    cast = lambda t: jax.tree.map(  # noqa: E731
        lambda x: x.astype(param_dtype), t)
    out = {
        "stages": stack_stages(cast(raw_params["layers"]), d_p,
                               cfg.spec.n_layers, v=v_stages),
        "embed": _pad_vocab(cast(raw_params["embed"]), d_s),
        "final_norm": cast(raw_params["final_norm"]),
    }
    if "unembed" in raw_params:
        out["unembed"] = _pad_vocab(cast(raw_params["unembed"]), d_s)
    return out


def param_pspecs(cfg: ArchConfig, params_shape: Dict, mesh: Mesh) -> Dict:
    pod, data, model = mesh_axis_names(mesh)
    d_s = mesh.shape[model]
    specs = {
        "stages": stage_param_specs(params_shape["stages"], d_s, pod=pod,
                                    data=data, model=model),
        "embed": P(model, None),
        "final_norm": P(model) if
        params_shape["final_norm"].shape[0] % d_s == 0 else P(),
    }
    if "unembed" in params_shape:
        specs["unembed"] = P(model, None)
    return specs


def batch_struct(geom: PipelineGeometry, n_pods: int) -> Dict:
    """ShapeDtypeStructs for one bucket's chunk buffers (global shapes)."""
    lead = (n_pods,) if n_pods > 1 else ()
    n, cap = geom.n_chunks, geom.cap
    i32 = jnp.int32
    return {
        "tokens": jax.ShapeDtypeStruct((*lead, n, cap), i32),
        "targets": jax.ShapeDtypeStruct((*lead, n, cap), i32),
        "seg": jax.ShapeDtypeStruct((*lead, n, cap), i32),
        "pos": jax.ShapeDtypeStruct((*lead, n, cap), i32),
        "ctx_len": jax.ShapeDtypeStruct((*lead, n), i32),
    }


@dataclass
class TrainStepBuilder:
    cfg: ArchConfig
    mesh: Mesh
    geom: PipelineGeometry
    adamw: AdamWConfig = field(default_factory=AdamWConfig)
    compress_pod_grads: bool = False
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        self.pod_axis, self.data_axis, self.model_axis = \
            mesh_axis_names(self.mesh)
        self.n_pods = self.mesh.shape[self.pod_axis] if self.pod_axis else 1

    # ------------------------------------------------------------------
    def init_params(self, key) -> Dict:
        model = DecoderLM(self.cfg)
        raw = model.init(key, jnp.float32)
        return prepare_params(self.cfg, raw, self.mesh, self.param_dtype,
                              v_stages=self.geom.v_stages)

    def abstract_params(self, key=None) -> Dict:
        key = key if key is not None else jax.random.PRNGKey(0)
        return jax.eval_shape(lambda k: self.init_params(k), key)

    def specs(self, params_shape) -> Tuple[Dict, Dict, Dict]:
        pspecs = param_pspecs(self.cfg, params_shape, self.mesh)
        ospecs = {"master": pspecs, "m": pspecs, "v": pspecs, "step": P()}
        bspecs = batch_specs(batch_struct(self.geom, self.n_pods),
                             pod=self.pod_axis, model=self.model_axis)
        return pspecs, ospecs, bspecs

    # ------------------------------------------------------------------
    def _norm_factors(self, pspecs) -> Any:
        """Per-leaf replication factor over the (data, model) axes — needed
        so the global grad norm counts every shard exactly once."""
        d_p = self.mesh.shape[self.data_axis]
        d_s = self.mesh.shape[self.model_axis]

        def fac(spec) -> float:
            names = {n for part in spec if part is not None
                     for n in ((part,) if isinstance(part, str) else part)}
            f = 1.0
            if self.data_axis not in names:
                f *= d_p
            if self.model_axis not in names:
                f *= d_s
            return f
        return jax.tree.map(fac, pspecs,
                            is_leaf=lambda x: isinstance(x, P))

    def _global_gnorm(self, grads, factors) -> jnp.ndarray:
        sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) / f
                 for g, f in zip(jax.tree.leaves(grads),
                                 jax.tree.leaves(factors)))
        sq = jax.lax.psum(sq, self.data_axis)
        sq = jax.lax.psum(sq, self.model_axis)
        return jnp.sqrt(sq)

    def _step_local(self, shard_dims, norm_factors, params, opt_state,
                    err_state, batch):
        cfg, geom = self.cfg, self.geom
        if self.pod_axis and self.n_pods > 1:
            batch = jax.tree.map(lambda x: x[0], batch)  # drop pod dim
        loss_fn = pipeline_loss_fn(
            cfg, geom, shard_dims, pod_axis=self.pod_axis,
            data_axis=self.data_axis, model_axis=self.model_axis)

        def objective(p):
            loss, n = loss_fn(p, batch)
            return loss, n

        (loss, n_valid), grads = jax.value_and_grad(
            objective, has_aux=True)(params)

        # head params are replicated across stages but used by a single
        # stage each; their true gradient is the sum over stages.
        for name in ("embed", "final_norm", "unembed"):
            if name in grads:
                grads[name] = jax.lax.psum(grads[name], self.data_axis)

        new_err = err_state
        if self.pod_axis and self.n_pods > 1:
            loss = jax.lax.psum(loss, self.pod_axis)
            n_valid = jax.lax.psum(n_valid, self.pod_axis)
            if self.compress_pod_grads:
                grads, new_err = compressed_psum(grads, err_state,
                                                 self.pod_axis)
            else:
                grads = jax.lax.psum(grads, self.pod_axis)

        grad_scale = 1.0 / jnp.maximum(n_valid, 1.0)
        gnorm = self._global_gnorm(grads, norm_factors)
        new_params, new_opt, metrics = adamw_update(
            self.adamw, params, grads, opt_state, grad_scale=grad_scale,
            gnorm=gnorm)
        metrics["loss"] = loss / jnp.maximum(n_valid, 1.0)
        metrics["tokens"] = n_valid
        return new_params, new_opt, new_err, metrics

    # ------------------------------------------------------------------
    def build(self, params_shape=None) -> Callable:
        params_shape = params_shape or self.abstract_params()
        pspecs, ospecs, bspecs = self.specs(params_shape)
        shard_dims = shard_dim_tree(params_shape["stages"],
                                    self.mesh.shape[self.model_axis])
        norm_factors = self._norm_factors(pspecs)

        mspec = {"loss": P(), "tokens": P(), "grad_norm": P(), "lr": P()}
        fn = functools.partial(self._step_local, shard_dims, norm_factors)
        mapped = jax.shard_map(
            fn, mesh=self.mesh,
            in_specs=(pspecs, ospecs,
                      pspecs if self.compress_pod_grads else None,
                      bspecs),
            out_specs=(pspecs, ospecs,
                       pspecs if self.compress_pod_grads else None,
                       mspec),
            check_vma=False)
        # donate the error-feedback state too: with compress_pod_grads its
        # leaves are params-sized and updated in place every step — leaving
        # them out doubles that footprint (program-donation lint finding).
        # Donating the None placeholder on the uncompressed path is a no-op.
        return jax.jit(mapped, donate_argnums=(0, 1, 2))

    def init_all(self, key):
        """Params and optimizer state, every leaf created in its mesh
        sharding: no device ever holds the whole model."""
        pspecs, ospecs, _ = self.specs(self.abstract_params(key))
        named = lambda t: jax.tree.map(  # noqa: E731
            lambda s: NamedSharding(self.mesh, s), t,
            is_leaf=lambda x: isinstance(x, P))

        def init(k):
            params = self.init_params(k)
            return params, init_opt_state(params)
        params, opt = jax.jit(init, out_shardings=(named(pspecs),
                                                   named(ospecs)))(key)
        err = init_error_state(params) if self.compress_pod_grads else None
        return params, opt, err
