"""Decoder-only EPP pipeline: a thin adapter over the shared stage-program
executor (DESIGN.md §2.1.1, runtime/executor.py).

Runs INSIDE ``shard_map`` over ("pod",) "data", "model":

* the "data" axis carries pipeline stages; stage p's layer parameters are
  the local shard of the stage-stacked tree;
* the tick loop, ppermute hand-off, remat split and streaming-CE folding
  are the executor core's; this module supplies the decoder-only hooks:
  embed injection at stage 0, per-layer ZeRO-3 gather + ``layer_apply``,
  and the split-chunk KV/SSM context carry appended at offset
  ``ctx_len[k]`` (a chunk with ctx_len == 0 implicitly resets the buffers
  and — explicitly — the SSM state);
* backward = the autodiff transpose of the scan: reverse tick order,
  reversed ppermute, and the context-carry cotangent reproduces the paper's
  dKV dependency (Eq. 5) exactly.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import DecoderLM, LayerCtx
from repro.models.config import ArchConfig
from repro.models.layers import rms_norm

from . import executor, sp
from .program import StageProgram
from .sharding import gather_layer_params, gather_stage_params

__all__ = ["PipelineGeometry", "pipeline_loss_fn", "gather_layer_params",
           "gather_stage_params", "init_stage_ctx"]


@dataclass(frozen=True)
class PipelineGeometry:
    """Static geometry of one compiled executable (a plan bucket)."""
    n_chunks: int            # chunks per pod
    cap: int                 # tokens per chunk (global, pre-SP-sharding)
    ctx_cap: int             # context buffer rows (policy layout dependent)
    d_p: int
    d_s: int
    l_ckpt: int              # max remat depth (uniform policy value)
    layers_per_stage: int
    policy: str              # "ulysses" | "allgather_kv" | "none"
    compute_dtype: Any = jnp.bfloat16
    # effective sequence-parallel degree (the plan's SP axis): tokens of a
    # chunk are sharded over d_s_eff SUB-GROUPS of the model axis and the
    # chunk's compute replicates d_s // d_s_eff times (sp.subgroup_info's
    # layout). 0 normalizes to the full d_s — the pre-SP-axis behavior.
    # Parameters, the vocab axis, and the batch's resting sharding stay
    # over the FULL model axis regardless.
    d_s_eff: int = 0
    # ZeRO-3 gather cadence: "per_tick" re-gathers every layer's weights for
    # every chunk (paper-faithful DeepSpeed ZeRO-3 semantics); "per_step"
    # gathers the stage's weights ONCE per training step and keeps them
    # resident (ZeRO-2-like compute path, ZeRO-3 storage) — the first
    # beyond-paper optimization, see EXPERIMENTS.md §Perf.
    zero3_mode: str = "per_tick"
    # schedule backend (core/schedule.py registry name) + virtual stages per
    # device. v_stages > 1 (interleaved-1f1b) splits each stage's layer
    # block into v virtual stages riding the same ppermute ring — it must
    # divide layers_per_stage and is baked into the parameter stacking
    # (sharding.interleaved_layer_order), so it is fixed per training run.
    schedule: str = "gpipe-1f1b"
    v_stages: int = 1
    # stage-aware adaptive checkpointing (Eq. 9-11): the solver's
    # per-(stage, chunk) layer-count matrix as a hashable (d_p, n_chunks)
    # tuple-of-tuples — None means the uniform policy (every tick remats
    # the leading l_ckpt layers via a static scan split). When set, each
    # tick looks its (stage, v_idx, chunk) depth up in traced arithmetic
    # (executor.remat_tick_count) and the whole table is baked into the
    # compiled step — which is why ExecutionPlan.bucket_key() carries the
    # table's digest.
    ckpt_table: Optional[Tuple[Tuple[int, ...], ...]] = None
    # zero-bubble split backward: B-grad ticks drop the weight-grad GEMMs
    # from the critical path (executor.split_backward_stage) and dedicated
    # W-drain ticks replay them during the backward cooldown. Train mode
    # only; runs under any schedule backend (the parity tests exercise all
    # three), defaulted on by zero-bubble-h1 in make_geometry.
    split_bwd: bool = False
    # double-buffered stage hand-off: the executor issues the stream
    # ppermute before the accumulator fold, so the fold (the vocab-parallel
    # CE matmul) overlaps the in-flight collective under the TPU compiler's
    # async collectives. Bitwise-identical results.
    overlap_handoff: bool = True

    def __post_init__(self) -> None:
        if self.v_stages < 1 or self.layers_per_stage % self.v_stages:
            raise ValueError(
                f"v_stages={self.v_stages} must divide "
                f"layers_per_stage={self.layers_per_stage}")
        if self.d_s_eff == 0:
            object.__setattr__(self, "d_s_eff", self.d_s)
        if self.d_s_eff < 1 or self.d_s % self.d_s_eff:
            raise ValueError(
                f"d_s_eff={self.d_s_eff} must divide d_s={self.d_s}")
        if self.policy == "ulysses" and self.d_s_eff == 1:
            raise ValueError("ulysses at d_s_eff=1 is meaningless; the "
                             "planner emits policy 'none' there")
        executor.canonical_ckpt_table(self.ckpt_table, d_p=self.d_p,
                                      n_chunks=self.n_chunks)

    @property
    def sp_rep(self) -> int:
        """Chunk-compute replication factor of the SP sub-grouping."""
        return self.d_s // self.d_s_eff


def init_stage_ctx(cfg: ArchConfig, geom: PipelineGeometry) -> LayerCtx:
    """Per-stage context carry. KV layout depends on the SP policy:
    ulysses => head-sharded [ctx_cap, Hkv/d_s_eff, Dh]; allgather_kv and
    "none" => replicated [ctx_cap, Hkv, Dh] (or MLA cache rows
    [ctx_cap, 1, r+rr])."""
    s = cfg.spec
    L_s = geom.layers_per_stage
    k = v = hh = tail = None
    if not s.attn_free:
        if s.kv_lora_rank > 0:
            kshape = (geom.ctx_cap, 1, s.kv_lora_rank + s.qk_rope_dim)
            vshape = (geom.ctx_cap, 1, 0)
        elif geom.policy == "ulysses":
            kshape = (geom.ctx_cap, s.n_kv_heads // geom.d_s_eff,
                      s.head_dim)
            vshape = kshape
        else:
            kshape = (geom.ctx_cap, s.n_kv_heads, s.head_dim)
            vshape = kshape
        k = jnp.zeros((L_s, *kshape), geom.compute_dtype)
        v = jnp.zeros((L_s, *vshape), geom.compute_dtype)
    if s.ssm_state > 0:
        di_loc = s.inner  # full: SSM is token-sharded, channels intact
        hh = jnp.zeros((L_s, di_loc, s.ssm_state), jnp.float32)
        tail = jnp.zeros((L_s, s.ssm_conv - 1, di_loc), geom.compute_dtype)
    return LayerCtx(k, v, hh, tail)


def _make_model(cfg: ArchConfig, geom: PipelineGeometry,
                model_axis: str) -> DecoderLM:
    rep, sp_groups, _ = sp.subgroup_info(geom.d_s, geom.d_s_eff)
    if geom.policy == "ulysses":
        attn = sp.make_ulysses_policy(model_axis, geom.d_s_eff,
                                      groups=sp_groups)
    elif geom.policy == "allgather_kv":
        attn = sp.make_allgather_kv_policy(model_axis, groups=sp_groups)
    else:
        # "none": with attention present this is d_s_eff == 1 — every
        # device holds the whole chunk, so DecoderLM's default LOCAL
        # policy is exactly right; attn-free archs never call it at all
        attn = None
    moe_fn = None
    if cfg.spec.n_experts > 0:
        from .ep import make_moe_ep
        moe_fn = make_moe_ep(model_axis, geom.d_s)
    ssm_scan = ssm_tail = None
    if cfg.spec.ssm_state > 0:
        from repro.models.ssm import _blocked_ssm
        ssm_scan = sp.make_sp_ssm_scan(model_axis, geom.d_s_eff,
                                       _blocked_ssm, groups=sp_groups,
                                       rep=rep)
        ssm_tail = sp.make_sp_conv_tail_exchange(model_axis, geom.d_s_eff,
                                                 rep=rep)
    return DecoderLM(cfg, attn_fn=attn, moe_fn=moe_fn,
                     ssm_scan_fn=ssm_scan, ssm_tail_exchange=ssm_tail)


def _run_stage_layers(model: DecoderLM, geom: PipelineGeometry,
                      stage_params, shard_dims, x, ctx: LayerCtx, *,
                      seg, pos, ctx_len, windows, active, model_axis: str,
                      n_layers: Optional[int] = None,
                      l_ckpt: Optional[Any] = None):
    """This backend's layer body under the executor's remat split:
    ZeRO-3 gather (per-tick mode), ``layer_apply`` with the context carry,
    and ``active`` masking padded layer slots into identity.

    ``n_layers``/``l_ckpt`` override the geometry defaults when the tick
    runs a single virtual-stage block instead of the whole stage;
    ``l_ckpt`` may be a traced scalar (the stage-aware per-(stage, chunk)
    lookup) — the executor then selects remat per layer at runtime."""

    def layer_body(x, per_layer):
        lp, w, act, lctx = per_layer
        lp_full = lp if geom.zero3_mode == "per_step" else \
            gather_layer_params(lp, shard_dims, model_axis)
        x_new, new_ctx = model.layer_apply(
            lp_full, x, pos=pos, seg=seg, ctx=lctx, ctx_len=ctx_len,
            window=w)
        x_out = jnp.where(act, x_new, x)
        new_ctx = jax.tree.map(
            lambda new, old: jnp.where(act, new, old) if new is not None
            else None, new_ctx, lctx, is_leaf=lambda t: t is None)
        return x_out, new_ctx

    return executor.run_stage_layers(
        layer_body, x, (stage_params, windows, active, ctx),
        l_ckpt=geom.l_ckpt if l_ckpt is None else l_ckpt,
        n_layers=(geom.layers_per_stage if n_layers is None else n_layers))


def pipeline_loss_fn(cfg: ArchConfig, geom: PipelineGeometry,
                     shard_dims, *,
                     pod_axis: Optional[str], data_axis: str = "data",
                     model_axis: str = "model",
                     mode: str = "train") -> Callable:
    """Returns loss_local(params, batch) to be called inside shard_map.

    params (local views):
      {"stages": stage-stacked layer tree [1, L_s, ...shards...],
       "embed": [V/d_s, D], "final_norm": [D or D/d_s],
       "unembed": optional [V/d_s, D]}
    batch (local views):
      {"tokens"/"targets"/"seg"/"pos": [n_chunks, cap/d_s],
       "ctx_len": [n_chunks]} (+ leading pod dim already sharded away)

    Returns (sum_loss, n_valid) replicated over data/model (psum'd).
    """
    if mode != "train" and geom.d_s_eff != geom.d_s:
        raise ValueError(
            f"mode={mode!r} requires d_s_eff == d_s "
            f"({geom.d_s_eff} != {geom.d_s}): the greedy fold's "
            "token-sharded gather assumes unreplicated shards")
    model = _make_model(cfg, geom, model_axis)
    rep, _, replica_groups = sp.subgroup_info(geom.d_s, geom.d_s_eff)
    s = cfg.spec
    v_st, L_s = geom.v_stages, geom.layers_per_stage
    L_v = L_s // v_st
    L_pad = geom.d_p * L_s
    import numpy as _np
    win_flat = _np.asarray([cfg.layer_window(i) for i in range(s.n_layers)]
                           + [0] * (L_pad - s.n_layers), _np.int32)
    act_flat = _np.arange(L_pad) < s.n_layers
    if v_st > 1:
        # virtual-stage placement: device p's local block (j, l) holds
        # global layer (j*d_p + p)*L_v + l — same order the params stack in
        from .sharding import interleaved_layer_order
        order = interleaved_layer_order(geom.d_p, L_s, v_st)
        win_flat, act_flat = win_flat[order], act_flat[order]
    windows_all = jnp.asarray(win_flat.reshape(geom.d_p, L_s))
    active_all = jnp.asarray(act_flat.reshape(geom.d_p, L_s))
    # stage-aware checkpointing: the solver's (d_p, n_chunks) table as a
    # baked-in constant; None keeps the uniform static-split path
    ckpt_tab = None if geom.ckpt_table is None else \
        jnp.asarray(geom.ckpt_table, jnp.int32)

    def loss_local(params, batch):
        p_idx = jax.lax.axis_index(data_axis)
        stage_params = jax.tree.map(lambda x: x[0], params["stages"])
        if geom.zero3_mode == "per_step":
            stage_params = gather_stage_params(stage_params, shard_dims,
                                               model_axis)
        windows = windows_all[p_idx]
        active = active_all[p_idx]
        n, d_p = geom.n_chunks, geom.d_p
        cap_loc = batch["tokens"].shape[-1]
        dt = geom.compute_dtype

        tokens_a = batch["tokens"].reshape(n, cap_loc)
        targets_a = batch["targets"].reshape(n, cap_loc)
        seg_a = batch["seg"].reshape(n, cap_loc)
        pos_a = batch["pos"].reshape(n, cap_loc)
        ctxlen_a = batch["ctx_len"].reshape(n)
        if rep > 1:
            # the batch rests sharded over the FULL model axis (cap/d_s
            # rows/device); at d_s_eff < d_s each device needs its
            # SUB-GROUP shard (cap/d_s_eff rows). The replica groups are
            # contiguous, so a tiled in-group gather concatenates the r
            # full-axis blocks back into the sub-group shard — replicated
            # across the r devices that share it. All-int arrays: no grad
            # flows through this gather.
            def _regather(t):
                return jax.lax.all_gather(t, model_axis, axis=1, tiled=True,
                                          axis_index_groups=replica_groups)
            tokens_a, targets_a, seg_a, pos_a = (
                _regather(tokens_a), _regather(targets_a),
                _regather(seg_a), _regather(pos_a))
            cap_loc *= rep

        # final-norm gamma may be feature-sharded; gather once
        fn_gamma = params["final_norm"]
        if fn_gamma.shape[0] != s.d_model:
            with jax.named_scope("ce_head"):
                fn_gamma = jax.lax.all_gather(fn_gamma, model_axis, axis=0,
                                              tiled=True)
        head_w = params.get("unembed", params["embed"])

        ctx0 = init_stage_ctx(cfg, geom)
        x0 = jnp.zeros((cap_loc, s.d_model), dt)
        split = geom.split_bwd and mode == "train"
        # a stage that recomputes every layer recomputes its CE head too:
        # the head's f32 norm residuals ([cap, D] per tick) outweigh a
        # layer input
        remat_head = (geom.ckpt_table is None
                      and geom.l_ckpt >= geom.layers_per_stage)

        def fold_acc(tc, x_out, ctx, acc):
            with jax.named_scope("ce_head"):
                return _fold_acc(tc, x_out, ctx, acc)

        def _fold_acc(tc, x_out, ctx, acc):  # noqa: ARG001 (ctx unused)
            """Fold one tick's output into the accumulator (CE / greedy
            ids). With ``overlap_handoff`` the executor calls this AFTER
            issuing the stream ppermute, so the vocab-parallel matmul here
            overlaps the in-flight collective (double-buffered hand-off).
            """
            seg = jnp.where(tc.valid, seg_a[tc.idxc], -1)
            if rep > 1:
                # the rep replicas of each sub-group computed identical
                # chunks; only the PRIMARY replica (replica index 0) folds
                # CE, so the full-axis psum inside sharded_ce counts every
                # token exactly once — non-primary copies contribute
                # exactly-zero loss AND exactly-zero cotangents (the mask
                # rides `seg`, which only the CE valid-test consumes here)
                primary = jax.lax.axis_index(model_axis) % rep == 0
                seg = jnp.where(primary, seg, -1)
            tgt = targets_a[tc.idxc]
            if mode == "train":
                # a fusion boundary: the CE fold compiles to the same
                # arithmetic whether it runs before or after the hand-off
                # ppermute is issued (without it XLA fuses the two
                # placements differently and the grads drift by an ULP)
                x_out, acc = jax.lax.optimization_barrier((x_out, acc))

                def head_ce(x_out, gamma, w, acc):
                    return executor.fold_streaming_ce(
                        tc, rms_norm(x_out, gamma, cfg.rms_eps), w, tgt,
                        seg, acc, model_axis=model_axis, vocab_true=s.vocab)
                if remat_head:
                    head_ce = jax.checkpoint(head_ce, prevent_cse=False)
                return head_ce(x_out, fn_gamma, head_w, acc)
            h_last = rms_norm(x_out, fn_gamma, cfg.rms_eps)
            # prefill: greedy next-token ids per position (the KV fills
            # the context carry — it IS the prefill cache). h_last is
            # token-sharded here, unlike decode's replicated rows.
            ids = executor.fold_greedy_ids(
                tc, h_last, head_w, acc[0],
                model_axis=model_axis, vocab_true=s.vocab,
                token_sharded=True)
            return (ids, acc[1])

        def _pack_aux(seg, pos, ctx_len, l_act, start=None):
            """Float32-cast pytree of the traced per-tick values the split
            stage closure needs: executor.split_backward_stage's backward
            is re-traced at scan-transpose time, so NOTHING traced may be
            closure-captured — it all rides through this explicit aux (and
            float32 keeps the cotangents ordinary zeros; every value here
            is an integer far below 2**24, so the round trip is exact)."""
            aux = {"seg": seg, "pos": pos, "ctx_len": ctx_len,
                   "windows": windows, "active": active}
            if l_act is not None and not isinstance(l_act, int):
                aux["l_ckpt"] = l_act
            if start is not None:
                aux["start"] = start
            return jax.tree.map(
                lambda a: jnp.asarray(a).astype(jnp.float32), aux)

        def _unpack_aux(af):
            i32 = lambda k: af[k].astype(jnp.int32)  # noqa: E731
            return (i32("seg"), i32("pos"), i32("ctx_len"), i32("windows"),
                    af["active"] > 0.5,
                    i32("l_ckpt") if "l_ckpt" in af else None,
                    i32("start") if "start" in af else None)

        def tick(tc, x_recv, ctx, acc, stash=None):
            with jax.named_scope("stage_tick"):
                return _tick(tc, x_recv, ctx, acc, stash)

        def _tick(tc, x_recv, ctx, acc, stash):
            tokens = tokens_a[tc.idxc]
            seg = jnp.where(tc.valid, seg_a[tc.idxc], -1)
            pos = pos_a[tc.idxc]
            ctx_len = jnp.where(tc.valid, ctxlen_a[tc.idxc], 0)

            x_emb = sp.sharded_embed(params["embed"], tokens, model_axis, dt)
            if cfg.embed_scale:
                x_emb = x_emb * jnp.asarray(s.d_model ** 0.5, dt)
            x_in = jnp.where(tc.is_first_stage, x_emb, x_recv)

            if v_st == 1:
                ctx_in = executor.reset_ssm_at_boundary(ctx, ctx_len)
                l_act = None if ckpt_tab is None else \
                    executor.remat_tick_count(ckpt_tab, tc.p_idx, tc.idxc,
                                              tc.valid)
                if split:
                    # zero-bubble B/W split: the custom_vjp drops the wgrad
                    # GEMMs from this tick's backward and stashes the
                    # boundary pair at the item's slot for the W drain
                    def sfn(xx, cc, pp, af):
                        a_seg, a_pos, a_cl, a_win, a_act, a_l, _ = \
                            _unpack_aux(af)
                        return _run_stage_layers(
                            model, geom, pp, shard_dims, xx, cc,
                            seg=a_seg, pos=a_pos, ctx_len=a_cl,
                            windows=a_win, active=a_act,
                            model_axis=model_axis,
                            l_ckpt=geom.l_ckpt if a_l is None else a_l)
                    x_out, ctx, stash = executor.split_backward_stage(
                        sfn, x_in, ctx_in, stage_params, stash,
                        tc.idxc, tc.valid,
                        aux=_pack_aux(seg, pos, ctx_len, l_act))
                else:
                    x_out, ctx = _run_stage_layers(
                        model, geom, stage_params, shard_dims, x_in, ctx_in,
                        seg=seg, pos=pos, ctx_len=ctx_len, windows=windows,
                        active=active, model_axis=model_axis, l_ckpt=l_act)
            else:
                # interleaved-1f1b: this tick runs ONE virtual stage — the
                # L_v-layer block (and its context-carry slice) at
                # tc.v_idx; everything else on the device stays untouched.
                start = tc.v_idx * L_v

                def _slc(t):
                    return jax.lax.dynamic_slice_in_dim(t, start, L_v, 0)

                ctx_v = jax.tree.map(
                    lambda t: _slc(t) if t is not None else None, ctx,
                    is_leaf=lambda t: t is None)
                ctx_v = executor.reset_ssm_at_boundary(ctx_v, ctx_len)
                # spread the solver's remat budget over the v virtual
                # blocks: ceil keeps total checkpointed layers >= the
                # stage's depth (memory-safe direction; over-remat bounded
                # by v - 1 layers, NOT v * l_ckpt). Stage-aware tables
                # look the (stage, chunk) depth up per tick first.
                l_act = min(-(-geom.l_ckpt // v_st), L_v) \
                    if ckpt_tab is None else \
                    executor.remat_tick_count(ckpt_tab, tc.p_idx, tc.idxc,
                                              tc.valid, v=v_st, l_max=L_v)
                if split:
                    # slot = (virtual stage, item); the stage_fn takes the
                    # FULL stage tree and slices inside (by the traced
                    # start riding aux), so the drain's weight grads land
                    # on the right block via the dynamic_slice transpose
                    def sfn(xx, cc, pp, af):
                        a_seg, a_pos, a_cl, a_win, a_act, a_l, a_st = \
                            _unpack_aux(af)

                        def _s(t):
                            return jax.lax.dynamic_slice_in_dim(
                                t, a_st, L_v, 0)
                        return _run_stage_layers(
                            model, geom, jax.tree.map(_s, pp),
                            shard_dims, xx, cc,
                            seg=a_seg, pos=a_pos, ctx_len=a_cl,
                            windows=_s(a_win), active=_s(a_act),
                            model_axis=model_axis, n_layers=L_v,
                            l_ckpt=l_act if a_l is None else a_l)
                    x_out, ctx_v, stash = executor.split_backward_stage(
                        sfn, x_in, ctx_v, stage_params, stash,
                        tc.v_idx * n + tc.idxc, tc.valid,
                        aux=_pack_aux(seg, pos, ctx_len, l_act, start))
                else:
                    x_out, ctx_v = _run_stage_layers(
                        model, geom, jax.tree.map(_slc, stage_params),
                        shard_dims, x_in, ctx_v,
                        seg=seg, pos=pos, ctx_len=ctx_len,
                        windows=_slc(windows), active=_slc(active),
                        model_axis=model_axis, n_layers=L_v,
                        l_ckpt=l_act)
                ctx = jax.tree.map(
                    lambda full, new: jax.lax.dynamic_update_slice_in_dim(
                        full, new, start, 0) if full is not None else None,
                    ctx, ctx_v, is_leaf=lambda t: t is None)

            if not geom.overlap_handoff:
                acc = fold_acc(tc, x_out, ctx, acc)
            if split:
                return x_out, ctx, acc, stash
            return x_out, ctx, acc

        def drain_tick(j, entry, sp_full, af):
            with jax.named_scope("stage_tick"):
                return _drain_tick(j, entry, sp_full, af)

        def _drain_tick(j, entry, sp_full, af):
            """W-grad tick ``j`` (transposed cooldown): replay stage
            weight grads for item ``j % n`` / virtual block ``j // n``
            from the stashed ``(x_in, ctx_in, ybar, ctx_bar)`` boundary
            pair. ``l_ckpt=0``: this IS a recompute, no nested remat.
            Batch lookups come through ``af`` (the float-cast drain aux) —
            custom_vjp hooks cannot close over traced values."""
            x_st, ctx_st, ybar, cbar = entry
            m = j % n
            seg = af["seg_a"].astype(jnp.int32)[m]
            pos = af["pos_a"].astype(jnp.int32)[m]
            ctx_len = af["ctxlen_a"].astype(jnp.int32)[m]
            d_win = af["windows"].astype(jnp.int32)
            d_act = af["active"] > 0.5
            if v_st == 1:
                def f(pp):
                    return _run_stage_layers(
                        model, geom, pp, shard_dims, x_st, ctx_st,
                        seg=seg, pos=pos, ctx_len=ctx_len, windows=d_win,
                        active=d_act, model_axis=model_axis, l_ckpt=0)
            else:
                start = (j // n) * L_v

                def _slcj(t):
                    return jax.lax.dynamic_slice_in_dim(t, start, L_v, 0)

                def f(pp):
                    return _run_stage_layers(
                        model, geom, jax.tree.map(_slcj, pp), shard_dims,
                        x_st, ctx_st,
                        seg=seg, pos=pos, ctx_len=ctx_len,
                        windows=_slcj(d_win), active=_slcj(d_act),
                        model_axis=model_axis, n_layers=L_v, l_ckpt=0)
            _, wv = jax.vjp(f, sp_full)
            (wbar,) = wv((ybar, cbar))
            return wbar

        if mode == "train":
            acc0: Tuple = (jnp.float32(0), jnp.float32(0))
        else:
            acc0 = (jnp.zeros((n, cap_loc), jnp.int32), jnp.float32(0))
        stash0 = None
        drain_aux = ()
        if split:
            ctx_struct = ctx0 if v_st == 1 else jax.tree.map(
                lambda t: t[:L_v], ctx0)
            stash0 = executor.make_stash(
                (x0, ctx_struct, x0, ctx_struct), n * v_st)
            drain_aux = jax.tree.map(
                lambda a: a.astype(jnp.float32),
                {"seg_a": seg_a, "pos_a": pos_a, "ctxlen_a": ctxlen_a,
                 "windows": windows, "active": active})
        program = StageProgram(n_items=n, d_p=d_p, data_axis=data_axis,
                               tick=tick, psum_acc=(mode == "train"),
                               schedule=geom.schedule, v=geom.v_stages,
                               fold=fold_acc if geom.overlap_handoff
                               else None,
                               split_bwd=split, init_stash=stash0,
                               drain_tick=drain_tick if split else None,
                               stage_params=stage_params if split else None,
                               drain_aux=drain_aux)
        xf, ctxf, acc = executor.run_stage_program(program, x0, ctx0, acc0)
        if mode == "train":
            # only the last stage accumulated loss; psum'd by the executor
            loss, n_val = acc
            return loss, n_val
        ids = jax.lax.psum(acc[0], data_axis)  # only last stage nonzero
        return ids, ctxf

    return loss_local
