"""Serving: pipelined decode (one new token against a KV cache) and
prefill (the forward pipeline whose context carry IS the cache).

Decode maps the assignment's decode_32k / long_500k shapes:

* the per-pod request batch splits into ``d_p`` microbatches that flow
  through the stage pipeline exactly like training chunks (ppermute ticks),
  so all stages stay busy — pipelined decode;
* the KV cache is sharded: stage dim over "data", sequence dim over
  "model"; decode attention is *flash-decode*: every "model" rank scores
  its local cache rows and the partial (m, l, acc) merge with a psum-LSE
  (works for any head count — kv=1 MQA included);
* the new token's KV row is written by the rank owning position
  ``cache_len``; SSM archs carry (h, conv_tail) instead — O(1) state;
* sliding-window layers (gemma3) mask rows outside the window (the cache
  is allocated full-length for shape uniformity; ring-buffer compaction is
  a recorded hillclimb lever — EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.models import DecoderLM
from repro.models.attention import mla_expand_ctx, project_qkv
from repro.models.config import ArchConfig, LayerKind
from repro.models.layers import rms_norm, swiglu_apply
from repro.models.ssm import dt_rank_of

from . import executor, sp
from .program import StageProgram
from .sharding import (gather_layer_params, mesh_axis_names,
                       shard_dim_tree)
from .train_step import param_pspecs, prepare_params

__all__ = ["DecodeGeometry", "decode_step_fn", "decode_state_struct",
           "DecodeStepBuilder",
           "EngineGeometry", "EngineStepBuilder", "make_engine_geometry",
           "engine_step_fn", "engine_pool_struct", "engine_pool_specs",
           "engine_batch_struct", "engine_copy_fn", "engine_copy_struct"]


def _dtype_name(dtype) -> str:
    """Canonical dtype string for bucket keys (the compiled HLO differs
    per compute dtype, so keys must too)."""
    import numpy as _np
    return _np.dtype(dtype).name


def _layer_tables(cfg: ArchConfig, d_p: int, L_s: int):
    """Per-stage ``[d_p, L_s]`` sliding-window sizes + active-layer mask
    (padded layer slots inactive) — shared by the decode and engine step
    builders so window/padding semantics can never diverge between the
    two serve paths."""
    import numpy as _np
    L_pad = d_p * L_s
    win = [cfg.layer_window(i) for i in range(cfg.spec.n_layers)]
    win += [0] * (L_pad - cfg.spec.n_layers)
    windows = jnp.asarray(win, jnp.int32).reshape(d_p, L_s)
    active = jnp.asarray(
        (_np.arange(L_pad) < cfg.spec.n_layers).reshape(d_p, L_s))
    return windows, active


@dataclass(frozen=True)
class DecodeGeometry:
    batch_per_pod: int
    cache_len: int                # S: current context size (static per bucket)
    d_p: int
    d_s: int
    layers_per_stage: int
    n_micro: int                  # microbatches (== d_p unless batch < d_p)
    compute_dtype: Any = jnp.bfloat16

    @property
    def bm(self) -> int:
        return max(1, self.batch_per_pod // self.n_micro)

    @property
    def s_cap(self) -> int:
        """Cache capacity: one extra row per shard so the new token's KV
        always has a home (position ``cache_len`` is written this step)."""
        return self.cache_len + self.d_s

    @property
    def s_loc(self) -> int:
        return self.s_cap // self.d_s

    @property
    def dtype_name(self) -> str:
        return _dtype_name(self.compute_dtype)


def make_decode_geometry(cfg: ArchConfig, mesh: Mesh, *, batch_per_pod: int,
                         cache_len: int,
                         compute_dtype=jnp.bfloat16) -> DecodeGeometry:
    pod, data, model = mesh_axis_names(mesh)
    d_p, d_s = mesh.shape[data], mesh.shape[model]
    n_micro = min(d_p, max(1, batch_per_pod))
    return DecodeGeometry(
        batch_per_pod=batch_per_pod, cache_len=cache_len, d_p=d_p, d_s=d_s,
        layers_per_stage=-(-cfg.spec.n_layers // d_p), n_micro=n_micro,
        compute_dtype=compute_dtype)


def decode_state_struct(cfg: ArchConfig, geom: DecodeGeometry,
                        n_pods: int) -> Dict:
    """Global ShapeDtypeStructs for the serving state (cache etc.)."""
    s = cfg.spec
    lead = (n_pods,) if n_pods > 1 else ()
    L_s, nm, bm = geom.layers_per_stage, geom.n_micro, geom.bm
    dt = geom.compute_dtype
    out: Dict[str, Any] = {
        "tokens": jax.ShapeDtypeStruct((*lead, nm, bm), jnp.int32),
    }
    if s.is_encoder_decoder:
        # stub memory: 1/4 of the decode context worth of encoder frames
        s_mem = max(geom.d_s, (geom.cache_len // 4) // geom.d_s * geom.d_s)
        out["memory"] = jax.ShapeDtypeStruct(
            (*lead, nm, bm, s_mem, s.d_model), dt)
    if not s.attn_free:
        if s.kv_lora_rank > 0:
            row = (s.kv_lora_rank + s.qk_rope_dim,)
            out["cache_k"] = jax.ShapeDtypeStruct(
                (*lead, geom.d_p, nm, L_s, bm, geom.s_cap, 1, *row), dt)
        else:
            out["cache_k"] = jax.ShapeDtypeStruct(
                (*lead, geom.d_p, nm, L_s, bm, geom.s_cap,
                 s.n_kv_heads, s.head_dim), dt)
            out["cache_v"] = jax.ShapeDtypeStruct(out["cache_k"].shape, dt)
    if s.ssm_state > 0:
        out["ssm_h"] = jax.ShapeDtypeStruct(
            (*lead, geom.d_p, nm, L_s, bm, s.inner, s.ssm_state), jnp.float32)
        out["conv_tail"] = jax.ShapeDtypeStruct(
            (*lead, geom.d_p, nm, L_s, bm, s.ssm_conv - 1, s.inner), dt)
    return out


def decode_state_specs(cfg: ArchConfig, geom: DecodeGeometry, *,
                       pod: Optional[str], data: str, model: str) -> Dict:
    s = cfg.spec
    lead = (pod,) if pod else ()
    out: Dict[str, Any] = {"tokens": P(*lead, None, None)}
    if s.is_encoder_decoder:
        # cross-attention memory [.., nm, bm, S_mem, D]: frames over model
        out["memory"] = P(*lead, None, None, model, None)
    if not s.attn_free:
        # [.., d_p, nm, L_s, bm, S, Hkv, Dh]: stage over data, seq over model
        out["cache_k"] = P(*lead, data, None, None, None, model, None, None)
        if s.kv_lora_rank == 0:
            out["cache_v"] = out["cache_k"]
    if s.ssm_state > 0:
        # channel dim over model
        out["ssm_h"] = P(*lead, data, None, None, None, model, None)
        out["conv_tail"] = P(*lead, data, None, None, None, None, model)
    return out


# ---------------------------------------------------------------------------
# Flash-decode attention (sequence-sharded cache, LSE merge over "model").
# ---------------------------------------------------------------------------

def _flash_decode(q, k_loc, v_loc, *, valid_rows, scale, model_axis):
    """q: [Bm, Hq, Dh]; k/v_loc: [Bm, S_loc, Hkv(+), Dh]; valid_rows:
    [S_loc] bool. Returns [Bm, Hq, Dv]."""
    Hq = q.shape[1]
    Hkv = k_loc.shape[2]
    if Hkv != Hq:
        rep = Hq // Hkv
        k_loc = jnp.repeat(k_loc, rep, axis=2)
        v_loc = jnp.repeat(v_loc, rep, axis=2)
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                   k_loc.astype(jnp.float32)) * scale
    s = jnp.where(valid_rows[None, None, :], s, -1e30)
    m = s.max(axis=-1)
    m_g = jax.lax.pmax(m, model_axis)
    p = jnp.exp(s - m_g[..., None])
    l = jax.lax.psum(p.sum(axis=-1), model_axis)
    acc = jnp.einsum("bhs,bshd->bhd", p, v_loc.astype(jnp.float32))
    acc = jax.lax.psum(acc, model_axis)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.where(l[..., None] > 0, out, 0.0).astype(q.dtype)


def decode_step_fn(cfg: ArchConfig, geom: DecodeGeometry, shard_dims, *,
                   pod_axis: Optional[str], data_axis: str = "data",
                   model_axis: str = "model") -> Callable:
    """Returns step_local(params, state) -> (next_ids [nm, bm], new state);
    call inside shard_map."""
    s = cfg.spec
    L_s, d_p, d_s = geom.layers_per_stage, geom.d_p, geom.d_s
    nm, bm = geom.n_micro, geom.bm
    dt = geom.compute_dtype
    S, S_loc = geom.cache_len, geom.s_loc
    windows_all, active_all = _layer_tables(cfg, d_p, L_s)
    scale = 1.0 / math.sqrt(s.head_dim + (s.qk_rope_dim if s.kv_lora_rank
                                          else 0)) if not s.attn_free else 0.0

    moe_fn = None
    if s.n_experts > 0:
        from .ep import make_moe_ep
        moe_fn = make_moe_ep(model_axis, d_s)

    def _attn_decode(lp, h, cache_k_l, cache_v_l, window):
        """One microbatch, one layer. h: [bm, D]."""
        pos = jnp.full((bm,), S, jnp.int32)
        q, k_new, v_new = project_qkv(cfg, lp, h, pos)
        # write the new row into the shard owning position S
        shard_off = jax.lax.axis_index(model_axis) * S_loc
        loc = S - shard_off
        ok = (loc >= 0) & (loc < S_loc)
        locc = jnp.clip(loc, 0, S_loc - 1)
        upd_k = jax.lax.dynamic_update_slice_in_dim(
            cache_k_l, k_new[:, None].astype(cache_k_l.dtype), locc, axis=1)
        cache_k_l = jnp.where(ok, upd_k, cache_k_l)
        if cache_v_l is not None:
            upd_v = jax.lax.dynamic_update_slice_in_dim(
                cache_v_l, v_new[:, None].astype(cache_v_l.dtype), locc,
                axis=1)
            cache_v_l = jnp.where(ok, upd_v, cache_v_l)
        rows = shard_off + jnp.arange(S_loc)
        valid = rows <= S
        big = jnp.int32(2 ** 30)
        w = jnp.where(window > 0, window, big)
        valid &= (S - rows) < w
        if s.kv_lora_rank > 0:
            kk, vv = jax.vmap(
                lambda c: mla_expand_ctx(cfg, lp, c))(cache_k_l)
            out = _flash_decode(q, kk, vv, valid_rows=valid, scale=scale,
                                model_axis=model_axis)
            out = out[..., :s.head_dim]
        else:
            out = _flash_decode(q, cache_k_l, cache_v_l, valid_rows=valid,
                                scale=scale, model_axis=model_axis)
        y = jnp.einsum("bh,hd->bd", out.reshape(bm, -1),
                       lp["wo"].astype(h.dtype))
        return y, cache_k_l, cache_v_l

    def _mamba_decode(lp, h, h_state, tail):
        """One-step SSM update; channels sharded over model.

        h: [bm, D]; h_state: [bm, di_loc, ds]; tail: [bm, K-1, di_loc].
        in/out projections are ZeRO-gathered full, so slice the local
        channel block."""
        di = s.inner
        di_loc = h_state.shape[-2]
        c_off = jax.lax.axis_index(model_axis) * di_loc
        dtr = dt_rank_of(cfg)
        xz = jnp.einsum("bd,dh->bh", h, lp["in_proj"].astype(h.dtype))
        xs_f, z_f = xz[:, :di], xz[:, di:]
        xs = jax.lax.dynamic_slice_in_dim(xs_f, c_off, di_loc, axis=1)
        z = jax.lax.dynamic_slice_in_dim(z_f, c_off, di_loc, axis=1)
        conv_w = jax.lax.dynamic_slice_in_dim(lp["conv_w"], c_off, di_loc, 1)
        conv_b = jax.lax.dynamic_slice_in_dim(lp["conv_b"], c_off, di_loc, 0)
        window = jnp.concatenate([tail, xs[:, None, :]], axis=1)  # [bm,K,dl]
        xc = jnp.einsum("bkd,kd->bd", window.astype(jnp.float32),
                        conv_w.astype(jnp.float32)) + conv_b.astype(jnp.float32)
        xc = jax.nn.silu(xc).astype(h.dtype)
        # x_proj/dt act on the full channel dim; gather local xc
        xc_full = jax.lax.all_gather(xc, model_axis, axis=1, tiled=True)
        proj = jnp.einsum("bd,dh->bh", xc_full, lp["x_proj"].astype(h.dtype))
        delta_in = proj[:, :dtr]
        Bv = proj[:, dtr:dtr + s.ssm_state].astype(jnp.float32)
        Cv = proj[:, dtr + s.ssm_state:dtr + 2 * s.ssm_state].astype(jnp.float32)
        delta_f = jax.nn.softplus(
            jnp.einsum("br,rd->bd", delta_in,
                       lp["dt_proj"].astype(h.dtype)).astype(jnp.float32)
            + lp["dt_bias"].astype(jnp.float32))
        delta = jax.lax.dynamic_slice_in_dim(delta_f, c_off, di_loc, axis=1)
        A = -jnp.exp(jax.lax.dynamic_slice_in_dim(
            lp["a_log"].astype(jnp.float32), c_off, di_loc, 0))
        a = jnp.exp(delta[..., None] * A[None])
        bx = (delta * xc.astype(jnp.float32))[..., None] * Bv[:, None, :]
        h_new = a * h_state + bx
        y = jnp.einsum("bds,bs->bd", h_new, Cv)
        dskip = jax.lax.dynamic_slice_in_dim(
            lp["d_skip"].astype(jnp.float32), c_off, di_loc, 0)
        y = y + dskip[None] * xc.astype(jnp.float32)
        y = y * jax.nn.silu(z.astype(jnp.float32))
        y_full = jax.lax.all_gather(y.astype(h.dtype), model_axis, axis=1,
                                    tiled=True)
        out = jnp.einsum("bd,dh->bh", y_full, lp["out_proj"].astype(h.dtype))
        new_tail = window[:, 1:, :].astype(tail.dtype)
        return out, h_new, new_tail

    def _cross_decode(lp, h, mem):
        """Cross-attention for one decode token per sequence.
        h: [bm, D]; mem: [bm, S_mem_loc, D] (frames sharded over model)."""
        Dh, Hq, Hkv = s.head_dim, s.n_heads, s.n_kv_heads
        dtl = h.dtype
        q = jnp.einsum("bd,dh->bh", h, lp["wq"].astype(dtl)
                       ).reshape(bm, Hq, Dh)
        k = jnp.einsum("bsd,dh->bsh", mem, lp["wk"].astype(dtl)
                       ).reshape(bm, -1, Hkv, Dh)
        v = jnp.einsum("bsd,dh->bsh", mem, lp["wv"].astype(dtl)
                       ).reshape(bm, -1, Hkv, Dh)
        valid = jnp.ones((k.shape[1],), bool)
        out = _flash_decode(q, k, v, valid_rows=valid,
                            scale=1.0 / math.sqrt(Dh),
                            model_axis=model_axis)
        return jnp.einsum("bh,hd->bd", out.reshape(bm, -1),
                          lp["wo"].astype(dtl))

    def step_local(params, state):
        p_idx = jax.lax.axis_index(data_axis)
        stage_params = jax.tree.map(lambda x: x[0], params["stages"])
        windows = windows_all[p_idx]
        active = active_all[p_idx]
        fn_gamma = params["final_norm"]
        if fn_gamma.shape[0] != s.d_model:
            fn_gamma = jax.lax.all_gather(fn_gamma, model_axis, axis=0,
                                          tiled=True)
        head_w = params.get("unembed", params["embed"])
        tokens = state["tokens"].reshape(nm, bm)  # lead dims are 1

        n_lead = (1 if pod_axis else 0) + 1  # (pod) + stage dims

        def sq(name):
            if name not in state:
                return None
            a = state[name]
            return a.reshape(a.shape[n_lead:]) if a is not None else None

        memory = None
        if "memory" in state:
            a = state["memory"]
            memory = a.reshape(a.shape[-(4):]) if pod_axis is None \
                else a.reshape(a.shape[-4:])
        cache_k = sq("cache_k")      # [nm, L_s, bm, S_loc, Hkv, Dh]
        cache_v = sq("cache_v")
        ssm_h = sq("ssm_h")          # [nm, L_s, bm, di_loc, ds]
        conv_tail = sq("conv_tail")

        def tick(tc, x_recv, state, out_ids):
            ck, cv, hh, tl = state
            idxc, valid = tc.idxc, tc.valid
            tok = tokens[idxc]
            x_emb = sp.sharded_embed(params["embed"], tok, model_axis, dt)
            if cfg.embed_scale:
                x_emb = x_emb * jnp.asarray(s.d_model ** 0.5, dt)
            x = jnp.where(tc.is_first_stage, x_emb, x_recv)

            new_ck, new_cv = ck, cv
            new_hh, new_tl = hh, tl
            for l in range(L_s):
                lp = gather_layer_params(
                    jax.tree.map(lambda a: a[l], stage_params),
                    shard_dims, model_axis)
                act = active[l]
                h_in = rms_norm(x, lp["ln1"], cfg.rms_eps)
                mix = jnp.zeros_like(x)
                if not s.attn_free and "attn" in lp:
                    ckl = ck[idxc, l] if ck is not None else None
                    cvl = cv[idxc, l] if cv is not None else None
                    y, ckl2, cvl2 = _attn_decode(lp["attn"], h_in, ckl, cvl,
                                                 windows[l])
                    if s.is_encoder_decoder and "cross" in lp:
                        hx = rms_norm(x + y, lp["ln_x"], cfg.rms_eps)
                        y = y + _cross_decode(lp["cross"], hx,
                                              memory[idxc])
                    if ck is not None:
                        new_ck = new_ck.at[idxc, l].set(
                            jnp.where(act & valid, ckl2, ckl))
                    if cv is not None:
                        new_cv = new_cv.at[idxc, l].set(
                            jnp.where(act & valid, cvl2, cvl))
                if s.ssm_state > 0:
                    y2, hh2, tl2 = _mamba_decode(lp["mamba"], h_in,
                                                 hh[idxc, l], tl[idxc, l])
                    if cfg.layer_kind == LayerKind.HYBRID:
                        mix = 0.5 * (mix + y2)
                    else:
                        mix = y2
                    new_hh = new_hh.at[idxc, l].set(
                        jnp.where(act & valid, hh2, hh[idxc, l]))
                    new_tl = new_tl.at[idxc, l].set(
                        jnp.where(act & valid, tl2, tl[idxc, l]))
                x_new = x + mix
                if cfg.layer_kind != LayerKind.MAMBA:
                    h2 = rms_norm(x_new, lp["ln2"], cfg.rms_eps)
                    if s.n_experts > 0:
                        x_new = x_new + moe_fn(cfg, lp["moe"], h2)
                    else:
                        x_new = x_new + swiglu_apply(lp["mlp"], h2)
                x = jnp.where(act, x_new, x)
                ck, cv, hh, tl = new_ck, new_cv, new_hh, new_tl

            h_last = rms_norm(x, fn_gamma, cfg.rms_eps)
            out_ids = executor.fold_greedy_ids(
                tc, h_last, head_w, out_ids,
                model_axis=model_axis, vocab_true=s.vocab)
            return x, (ck, cv, hh, tl), out_ids

        x0 = jnp.zeros((bm, s.d_model), dt)
        ids0 = jnp.zeros((nm, bm), jnp.int32)
        program = StageProgram(n_items=nm, d_p=d_p, data_axis=data_axis,
                               tick=tick, psum_acc=True)
        xf, (ck, cv, hh, tl), out_ids = executor.run_stage_program(
            program, x0, (cache_k, cache_v, ssm_h, conv_tail), ids0)

        new_state = dict(state)
        new_state["tokens"] = out_ids.reshape(state["tokens"].shape)

        def unsq(a, ref):
            return a.reshape(ref.shape) if a is not None else None
        if "cache_k" in state:
            new_state["cache_k"] = unsq(ck, state["cache_k"])
        if "cache_v" in state:
            new_state["cache_v"] = unsq(cv, state["cache_v"])
        if "ssm_h" in state:
            new_state["ssm_h"] = unsq(hh, state["ssm_h"])
        if "conv_tail" in state:
            new_state["conv_tail"] = unsq(tl, state["conv_tail"])
        return out_ids, new_state

    return step_local


# ===========================================================================
# Continuous-batching serving engine: one stage program for chunked prefill
# AND k-token (speculative) decode over a PAGED, sequence-sharded KV pool.
#
# The unit of work is a *packed token chunk* — the trainer's chunk
# abstraction reborn for serving. Every engine-step item is a fixed-shape
# buffer of ``cap_t`` tokens carrying per-token metadata:
#
#   tokens[t]    the token id fed at this position
#   pos[t]       absolute position in the owning sequence; the cache home of
#                this token's KV row is page ``pages[t, pos // page_sz]``,
#                row ``pos % page_sz``
#   seg[t]       item-local segment id (-1 = padding); intra-chunk attention
#                is same-segment causal
#   ctx_base[t]  committed cache rows of the segment at step start; cache
#                attention sees logical rows [0, ctx_base) only
#   pages[t, e]  the owning request's page table (``n_pages`` = sentinel:
#                unmapped entries; padding and bubble-tick writes land in
#                the trash page). Replicated over the model axis — every
#                rank serves the pages IT owns for all cap_t tokens.
#
# The pool is PAGE-granular and sequence-sharded: global page id
# ``p ∈ [0, n_pages)`` lives on model-rank ``p // n_pages_loc`` at local
# index ``p % n_pages_loc``; each rank also keeps one local trash page.
# Capacity therefore scales with the model axis (d_s ranks hold d_s× the
# pages of one device), at the cost of each rank scoring all cap_t queries
# against its local pages — the partial (m, l, acc) merge with the
# flash-decode psum-LSE, plus the intra-chunk rows computed replicated.
#
# A prefill chunk is a segment of prompt tokens (pos = offset..offset+c-1,
# ctx_base = offset); a decode tick is a segment of k tokens (the last
# accepted token + k-1 draft tokens, ctx_base = committed length). Both run
# the SAME compiled program: per token, attention = LSE-merge over
# [page-gathered cache rows ‖ intra-chunk same-segment causal rows], then
# the token's KV row is scattered into (page, pos % page_sz) by the rank
# owning the page. Rows at pos >= ctx_base written by rejected drafts are
# invisible (masked) until overwritten.
#
# Per-stream lengths are DATA, not shape: one executable serves every
# request mix, so the engine's bucket-key set is closed
# (compile_cache.engine_bucket_key + engine_copy_bucket_key — the second
# program is the copy-on-write page copy below). Decode runs remat-free
# (static l_ckpt=0 — the ROADMAP's per-chunk remat-free decode item).
# ===========================================================================


@dataclass(frozen=True)
class EngineGeometry:
    """Static geometry of one compiled engine step (a serve bucket)."""
    n_items: int             # packed chunk items per engine step
    cap_t: int               # tokens per item (global; sharded over model)
    n_pages: int             # user KV pages pool-wide (n_pages % d_s == 0)
    page_sz: int             # cache rows per page
    pages_per_seq: int       # page-table entries per request (max context)
    k: int                   # decode tokens per stream per step (1 = greedy)
    d_p: int
    d_s: int
    layers_per_stage: int
    copy_cap: int = 4        # COW page copies per copy-program call
    compute_dtype: Any = jnp.bfloat16

    @property
    def trash_page(self) -> int:
        """Sentinel page id: unmapped table entries, padding/bubble writes."""
        return self.n_pages

    @property
    def n_pages_loc(self) -> int:
        """User pages resident per model rank (+1 local trash page)."""
        return self.n_pages // self.d_s

    @property
    def max_ctx(self) -> int:
        """Rows a full page table can address (max prompt + generated)."""
        return self.pages_per_seq * self.page_sz

    @property
    def dtype_name(self) -> str:
        return _dtype_name(self.compute_dtype)


def make_engine_geometry(cfg: ArchConfig, mesh: Mesh, *, n_items: int,
                         cap_t: int, n_pages: int, page_sz: int,
                         pages_per_seq: Optional[int] = None, k: int = 1,
                         copy_cap: int = 4,
                         compute_dtype=jnp.bfloat16) -> EngineGeometry:
    s = cfg.spec
    if s.attn_free or s.ssm_state > 0:
        raise NotImplementedError(
            "serving engine supports attention archs only (SSM/hybrid decode "
            "uses the one-shot decode_step_fn path)")
    if s.is_encoder_decoder:
        raise NotImplementedError("serving engine is decoder-only")
    if s.kv_lora_rank > 0:
        raise NotImplementedError(
            "MLA latent cache rows are not wired into the page pool yet "
            "(see ROADMAP follow-ons)")
    pod, data, model = mesh_axis_names(mesh)
    if pod is not None:
        raise NotImplementedError("engine runs on a (data, model) mesh; "
                                  "multi-pod request routing is a ROADMAP "
                                  "follow-on")
    d_p, d_s = mesh.shape[data], mesh.shape[model]
    if cap_t % d_s:
        raise ValueError(f"cap_t={cap_t} must be divisible by the model "
                         f"axis d_s={d_s}")
    if min(n_items, cap_t, n_pages, page_sz, k, copy_cap) < 1:
        raise ValueError(
            "n_items/cap_t/n_pages/page_sz/k/copy_cap must all be >= 1")
    if n_pages % d_s:
        raise ValueError(f"n_pages={n_pages} must be divisible by the model "
                         f"axis d_s={d_s} (the pool is sequence-sharded "
                         f"page-blockwise)")
    pp = n_pages if pages_per_seq is None else pages_per_seq
    if not (1 <= pp <= n_pages):
        raise ValueError(f"pages_per_seq={pp} must be in [1, n_pages="
                         f"{n_pages}]")
    if k > cap_t:
        raise ValueError(f"k={k} cannot exceed cap_t={cap_t}")
    return EngineGeometry(
        n_items=n_items, cap_t=cap_t, n_pages=n_pages, page_sz=page_sz,
        pages_per_seq=pp, k=k, d_p=d_p, d_s=d_s,
        layers_per_stage=-(-cfg.spec.n_layers // d_p), copy_cap=copy_cap,
        compute_dtype=compute_dtype)


def engine_pool_struct(cfg: ArchConfig, geom: EngineGeometry) -> Dict:
    """Global ShapeDtypeStructs of the paged KV pool: per stage (d_p over
    "data"), per layer, ``n_pages + d_s`` pages of ``page_sz`` rows with the
    page axis sharded over the model axis — each rank holds its
    ``n_pages_loc`` user pages plus one local trash page (the last local
    index), so at d_s=1 the shape is exactly ``[d_p, L_s, n_pages + 1,
    page_sz, Hkv, Dh]``. Capacity scales with the mesh: pages are NOT
    replicated."""
    s = cfg.spec
    shape = (geom.d_p, geom.layers_per_stage, geom.n_pages + geom.d_s,
             geom.page_sz, s.n_kv_heads, s.head_dim)
    st = jax.ShapeDtypeStruct(shape, geom.compute_dtype)
    return {"cache_k": st, "cache_v": st}


def engine_pool_specs(data: str = "data", model: str = "model") -> Dict:
    p = P(data, None, model, None, None, None)
    return {"cache_k": p, "cache_v": p}


def engine_batch_struct(geom: EngineGeometry) -> Dict:
    """Per-step packed chunk buffers (global shapes; token dim sharded over
    the model axis like the trainer's chunk buffers, the page table
    replicated — see ``sharding.batch_specs(replicated=("pages",))``)."""
    n, c = geom.n_items, geom.cap_t
    st = jax.ShapeDtypeStruct((n, c), jnp.int32)
    return {"tokens": st, "pos": st, "seg": st, "ctx_base": st,
            "pages": jax.ShapeDtypeStruct((n, c, geom.pages_per_seq),
                                          jnp.int32)}


def engine_copy_struct(geom: EngineGeometry) -> Dict:
    """Copy-program operands: ``copy_cap`` (src, dst) global page-id pairs;
    ``n_pages`` sentinels are no-ops, so one fixed-shape program serves any
    number of copy-on-write copies per step."""
    st = jax.ShapeDtypeStruct((geom.copy_cap,), jnp.int32)
    return {"src": st, "dst": st}


def _paged_attention(q, k_page, v_page, k_intra, v_intra, ok_page,
                     ok_intra, *, scale, model_axis):
    """Per-token attention over [page-gathered cache rows ‖ intra rows].

    q: [T, Hq, Dh] (all cap_t queries, every rank); k/v_page:
    [T, R, Hkv, Dh] — THIS rank's resident rows for each token's page table
    (R = pages_per_seq * page_sz); k/v_intra: [T, Hkv, Dh] (the whole
    chunk, replicated); ok_page: [T, R] bool (false off-rank); ok_intra:
    [T, T] bool. Cache partials (m, l, acc) merge across the model axis
    with the flash-decode psum-LSE; intra contributions are replicated and
    added once. Returns [T, Hq, Dh] on every rank."""
    Hq, Hkv = q.shape[1], k_intra.shape[1]
    if Hkv != Hq:
        rep = Hq // Hkv
        k_page = jnp.repeat(k_page, rep, axis=2)
        v_page = jnp.repeat(v_page, rep, axis=2)
        k_intra = jnp.repeat(k_intra, rep, axis=1)
        v_intra = jnp.repeat(v_intra, rep, axis=1)
    qf = q.astype(jnp.float32)
    s_c = jnp.einsum("thd,tshd->ths", qf,
                     k_page.astype(jnp.float32)) * scale
    s_i = jnp.einsum("thd,shd->ths", qf,
                     k_intra.astype(jnp.float32)) * scale
    s_c = jnp.where(ok_page[:, None, :], s_c, -1e30)
    s_i = jnp.where(ok_intra[:, None, :], s_i, -1e30)
    m_c = jax.lax.pmax(s_c.max(axis=-1), model_axis)
    m = jnp.maximum(m_c, s_i.max(axis=-1))        # same on every rank
    p_c = jnp.exp(s_c - m[..., None])
    p_i = jnp.exp(s_i - m[..., None])
    l = jax.lax.psum(p_c.sum(axis=-1), model_axis) + p_i.sum(axis=-1)
    acc = jax.lax.psum(
        jnp.einsum("ths,tshd->thd", p_c, v_page.astype(jnp.float32)),
        model_axis)
    acc = acc + jnp.einsum("ths,shd->thd", p_i,
                           v_intra.astype(jnp.float32))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


def engine_step_fn(cfg: ArchConfig, geom: EngineGeometry, shard_dims, *,
                   data_axis: str = "data",
                   model_axis: str = "model") -> Callable:
    """Returns step_local(params, pool, batch) -> (ids [n, cap_loc], pool');
    call inside shard_map. ``ids[i, t]`` is the greedy next-token id after
    consuming batch token ``(i, t)`` (the same fold the prefill path uses);
    the host reads decode/prefill outputs at its packed offsets."""
    s = cfg.spec
    L_s, d_p, d_s = geom.layers_per_stage, geom.d_p, geom.d_s
    n = geom.n_items
    ps, pp = geom.page_sz, geom.pages_per_seq
    n_loc = geom.n_pages_loc
    dt = geom.compute_dtype
    windows_all, active_all = _layer_tables(cfg, d_p, L_s)
    scale = 1.0 / math.sqrt(s.head_dim)
    moe_fn = None
    if s.n_experts > 0:
        from .ep import make_moe_ep
        moe_fn = make_moe_ep(model_axis, d_s)

    def step_local(params, pool, batch):
        p_idx = jax.lax.axis_index(data_axis)
        m_idx = jax.lax.axis_index(model_axis)
        stage_params = jax.tree.map(lambda x: x[0], params["stages"])
        windows = windows_all[p_idx]
        active = active_all[p_idx]
        fn_gamma = params["final_norm"]
        if fn_gamma.shape[0] != s.d_model:
            fn_gamma = jax.lax.all_gather(fn_gamma, model_axis, axis=0,
                                          tiled=True)
        head_w = params.get("unembed", params["embed"])
        cap_loc = batch["tokens"].shape[-1]

        tokens_a = batch["tokens"].reshape(n, cap_loc)
        pos_a = batch["pos"].reshape(n, cap_loc)
        seg_a = batch["seg"].reshape(n, cap_loc)
        base_a = batch["ctx_base"].reshape(n, cap_loc)
        pages_a = batch["pages"].reshape(n, geom.cap_t, pp)   # replicated

        # local pool view: drop the stage dim sharded over "data"; the page
        # axis is already the LOCAL n_loc + 1 block of this model rank
        ck0 = pool["cache_k"].reshape(pool["cache_k"].shape[1:])
        cv0 = pool["cache_v"].reshape(pool["cache_v"].shape[1:])
        # logical row of gathered-page entry (e, r): e * page_sz + r
        rows_log = jnp.arange(pp * ps)
        big = jnp.int32(2 ** 30)

        def tick(tc, x_recv, state, ids_acc):
            ck, cv = state
            idxc = tc.idxc
            tok = tokens_a[idxc]
            seg_l = jnp.where(tc.valid, seg_a[idxc], -1)
            pos_l = pos_a[idxc]
            # full-chunk metadata: intra attention, the paged gathers and
            # the page-owner writes need every rank to see all cap_t rows
            seg_g = jax.lax.all_gather(seg_l, model_axis, axis=0, tiled=True)
            pos_g = jax.lax.all_gather(pos_l, model_axis, axis=0, tiled=True)
            base_g = jax.lax.all_gather(base_a[idxc], model_axis, axis=0,
                                        tiled=True)
            pages_t = pages_a[idxc]                        # [cap_t, pp]
            # which table entries live on THIS rank (sentinel n_pages maps
            # to owner d_s — never a real rank — so it is off-rank
            # everywhere and reads mask out / writes trash below)
            owner = pages_t // n_loc
            mine = owner == m_idx
            loc = jnp.where(mine, pages_t % n_loc, n_loc)  # n_loc = trash
            mine_rows = jnp.repeat(mine, ps, axis=1)       # [cap_t, pp*ps]

            x_emb = sp.sharded_embed(params["embed"], tok, model_axis, dt)
            if cfg.embed_scale:
                x_emb = x_emb * jnp.asarray(s.d_model ** 0.5, dt)
            x = jnp.where(tc.is_first_stage, x_emb, x_recv)

            # write targets: the page holding row ``pos`` per token; tokens
            # past the table (pos >= max_ctx), padding, bubble ticks and
            # unmapped entries all land in the LOCAL trash page
            entry_w = jnp.clip(pos_g // ps, 0, pp - 1)
            pid_w = jnp.take_along_axis(pages_t, entry_w[:, None],
                                        axis=1)[:, 0]
            row_w = jnp.clip(pos_g % ps, 0, ps - 1)

            def layer_body(x, per_layer):
                lp, w, act, ck_l, cv_l = per_layer
                lp = gather_layer_params(lp, shard_dims, model_axis)
                h_in = rms_norm(x, lp["ln1"], cfg.rms_eps)
                q, k_new, v_new = project_qkv(cfg, lp["attn"], h_in, pos_l)
                q_g = jax.lax.all_gather(q, model_axis, axis=0, tiled=True)
                k_g = jax.lax.all_gather(k_new, model_axis, axis=0,
                                         tiled=True)
                v_g = jax.lax.all_gather(v_new, model_axis, axis=0,
                                         tiled=True)
                w_eff = jnp.where(w > 0, w, big)
                # this rank's resident rows of each token's page table:
                # [cap_t, pp, page_sz, Hkv, Dh] -> flatten the page dims
                kc = ck_l[loc].reshape(geom.cap_t, pp * ps, *ck_l.shape[2:])
                vc = cv_l[loc].reshape(geom.cap_t, pp * ps, *cv_l.shape[2:])
                # cache rows: committed prefix, resident here, window-masked
                ok_c = mine_rows \
                    & (rows_log[None, :] < base_g[:, None]) \
                    & (seg_g >= 0)[:, None] \
                    & ((pos_g[:, None] - rows_log[None, :]) < w_eff)
                # intra-chunk: same segment, causal, window-masked
                ok_i = (seg_g[None, :] == seg_g[:, None]) \
                    & (seg_g >= 0)[:, None] \
                    & (pos_g[None, :] <= pos_g[:, None]) \
                    & ((pos_g[:, None] - pos_g[None, :]) < w_eff)
                out = _paged_attention(q_g, kc, vc, k_g, v_g, ok_c, ok_i,
                                       scale=scale, model_axis=model_axis)
                # every rank computed all cap_t outputs; keep my token block
                out_l = jax.lax.dynamic_slice_in_dim(
                    out, m_idx * cap_loc, cap_loc, axis=0)
                y = jnp.einsum("th,hd->td",
                               out_l.reshape(out_l.shape[0], -1),
                               lp["attn"]["wo"].astype(x.dtype))
                # scatter the chunk's KV rows into (page, pos % page_sz):
                # only the page's owner writes; everything else trashes
                w_ok = (seg_g >= 0) & tc.valid & act \
                    & (pos_g < pp * ps) & ((pid_w // n_loc) == m_idx)
                page_w = jnp.where(w_ok, pid_w % n_loc, n_loc)
                ck_l = ck_l.at[page_w, row_w].set(k_g.astype(ck_l.dtype))
                cv_l = cv_l.at[page_w, row_w].set(v_g.astype(cv_l.dtype))
                x_new = x + y
                h2 = rms_norm(x_new, lp["ln2"], cfg.rms_eps)
                if s.n_experts > 0:
                    x_new = x_new + moe_fn(cfg, lp["moe"], h2)
                else:
                    x_new = x_new + swiglu_apply(lp["mlp"], h2)
                x = jnp.where(act, x_new, x)
                return x, (ck_l, cv_l)

            # remat-free: serving never differentiates, so l_ckpt=0 keeps
            # the plain single-scan layer path
            x_out, (ck, cv) = executor.run_stage_layers(
                layer_body, x, (stage_params, windows, active, ck, cv),
                l_ckpt=0, n_layers=L_s)
            h_last = rms_norm(x_out, fn_gamma, cfg.rms_eps)
            ids_acc = executor.fold_greedy_ids(
                tc, h_last, head_w, ids_acc,
                model_axis=model_axis, vocab_true=s.vocab,
                token_sharded=True)
            return x_out, (ck, cv), ids_acc

        x0 = jnp.zeros((cap_loc, s.d_model), dt)
        ids0 = jnp.zeros((n, cap_loc), jnp.int32)
        program = StageProgram(n_items=n, d_p=d_p, data_axis=data_axis,
                               tick=tick, psum_acc=True)
        _, (ck, cv), ids = executor.run_stage_program(
            program, x0, (ck0, cv0), ids0)
        new_pool = {"cache_k": ck.reshape(pool["cache_k"].shape),
                    "cache_v": cv.reshape(pool["cache_v"].shape)}
        return ids, new_pool

    return step_local


def engine_copy_fn(geom: EngineGeometry, *,
                   model_axis: str = "model") -> Callable:
    """Device-side page copy for copy-on-write: returns
    copy_local(pool, copies) -> pool' for use inside shard_map.

    ``copies`` is {"src", "dst"}: ``copy_cap`` global page-id pairs
    (sentinel ``n_pages`` pairs are no-ops). For each pair the source
    owner broadcasts the page over the model axis (psum of a single
    non-zero contribution) and the destination owner writes it — src and
    dst may live on different ranks. Each pipeline stage copies its own
    layer slab; no data-axis collectives."""
    n_loc = geom.n_pages_loc

    def copy_local(pool, copies):
        m_idx = jax.lax.axis_index(model_axis)
        ck = pool["cache_k"].reshape(pool["cache_k"].shape[1:])
        cv = pool["cache_v"].reshape(pool["cache_v"].shape[1:])

        def body(carry, sd):
            ck, cv = carry
            src, dst = sd
            s_mine = (src // n_loc) == m_idx      # sentinel: no owner
            s_loc = jnp.where(s_mine, src % n_loc, n_loc)
            pk = jax.lax.psum(
                jnp.where(s_mine, ck[:, s_loc], 0), model_axis)
            pv = jax.lax.psum(
                jnp.where(s_mine, cv[:, s_loc], 0), model_axis)
            d_mine = (dst // n_loc) == m_idx
            d_loc = jnp.where(d_mine, dst % n_loc, n_loc)
            ck = ck.at[:, d_loc].set(
                jnp.where(d_mine, pk.astype(ck.dtype), ck[:, d_loc]))
            cv = cv.at[:, d_loc].set(
                jnp.where(d_mine, pv.astype(cv.dtype), cv[:, d_loc]))
            return (ck, cv), None

        (ck, cv), _ = jax.lax.scan(body, (ck, cv),
                                   (copies["src"], copies["dst"]))
        return {"cache_k": ck.reshape(pool["cache_k"].shape),
                "cache_v": cv.reshape(pool["cache_v"].shape)}

    return copy_local


@dataclass
class EngineStepBuilder:
    """Builds the AOT-compiled engine step for a mesh + engine geometry.

    AOT (``lower().compile()``) so the executable is serializable into the
    persistent :class:`~repro.runtime.cache_store.CacheStore` — a serving
    restart warm-starts its two engine buckets (step + COW page copy)."""
    cfg: ArchConfig
    mesh: Mesh
    geom: EngineGeometry
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        self.pod_axis, self.data_axis, self.model_axis = \
            mesh_axis_names(self.mesh)
        if self.pod_axis is not None:
            raise NotImplementedError("engine runs on a (data, model) mesh")

    # ------------------------------------------------------------------
    def init_params(self, key) -> Dict:
        raw = DecoderLM(self.cfg).init(key, jnp.float32)
        return prepare_params(self.cfg, raw, self.mesh, self.param_dtype)

    def abstract_params(self, key=None) -> Dict:
        key = key if key is not None else jax.random.PRNGKey(0)
        return jax.eval_shape(lambda k: self.init_params(k), key)

    def init_pool(self) -> Dict:
        return {k: jnp.zeros(v.shape, v.dtype)
                for k, v in engine_pool_struct(self.cfg, self.geom).items()}

    # ------------------------------------------------------------------
    def build(self, params_shape=None):
        params_shape = params_shape or self.abstract_params()
        pspecs = param_pspecs(self.cfg, params_shape, self.mesh)
        shard_dims = shard_dim_tree(params_shape["stages"],
                                    self.mesh.shape[self.model_axis])
        from .sharding import batch_specs
        bspecs = batch_specs(engine_batch_struct(self.geom), pod=None,
                             model=self.model_axis, replicated=("pages",))
        poolspecs = engine_pool_specs(self.data_axis, self.model_axis)
        fn = engine_step_fn(self.cfg, self.geom, shard_dims,
                            data_axis=self.data_axis,
                            model_axis=self.model_axis)
        mapped = jax.shard_map(
            fn, mesh=self.mesh,
            in_specs=(pspecs, poolspecs, bspecs),
            out_specs=(P(None, self.model_axis), poolspecs),
            check_vma=False)
        pool_struct = engine_pool_struct(self.cfg, self.geom)
        batch_struct_ = engine_batch_struct(self.geom)
        return jax.jit(mapped).lower(
            params_shape, pool_struct, batch_struct_).compile()

    def build_copy(self):
        """AOT-compile the COW page-copy program (its own cache bucket —
        see ``compile_cache.engine_copy_bucket_key``)."""
        poolspecs = engine_pool_specs(self.data_axis, self.model_axis)
        cspecs = {"src": P(None), "dst": P(None)}
        fn = engine_copy_fn(self.geom, model_axis=self.model_axis)
        mapped = jax.shard_map(
            fn, mesh=self.mesh, in_specs=(poolspecs, cspecs),
            out_specs=poolspecs, check_vma=False)
        pool_struct = engine_pool_struct(self.cfg, self.geom)
        return jax.jit(mapped).lower(
            pool_struct, engine_copy_struct(self.geom)).compile()
