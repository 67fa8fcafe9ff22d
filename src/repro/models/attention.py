"""Attention family: GQA (+RoPE/M-RoPE/qk-norm/sliding window) and MLA.

The functions are deliberately granular — projection, rope, core attention
and output projection are separate — because the distributed runtime
(`repro.runtime.sp`) splices its all-to-all / all-gather collectives between
projection and the attention core. The reference single-device path simply
composes them.

Packed-varlen semantics: a chunk is a flat token buffer ``[T]`` with
``seg_ids`` (segment id per token, -1 = padding) and ``pos_ids`` (position
within the owning sequence). Split-chunk context arrives as KV buffers of
capacity ``C_cap`` whose first ``ctx_len`` entries are valid; context tokens
belong to segment 0 (the chunking layer guarantees the split slice is
segment 0) and carry positions ``0..ctx_len-1``.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ArchConfig
from .layers import apply_mrope, apply_rope, dense_init, rms_norm

__all__ = ["init_attention", "attention_block", "project_qkv",
           "mla_expand_ctx", "make_local_attention_policy", "AttnFn"]

# attn_fn(q[T,Hq,Dh], k[S,Hkv,Dh], v[S,Hkv,Dh], seg_q[T], seg_kv[S],
#         pos_q[T], pos_kv[S], *, causal, window, scale) -> [T,Hq,Dh]
AttnFn = Callable[..., jnp.ndarray]


def init_attention(cfg: ArchConfig, key, dtype=jnp.float32) -> Dict:
    s = cfg.spec
    D, Dh, Hq, Hkv = s.d_model, s.head_dim, s.n_heads, s.n_kv_heads
    ks = jax.random.split(key, 8)
    if s.kv_lora_rank > 0:  # MLA
        r, rr = s.kv_lora_rank, s.qk_rope_dim
        p = {
            "wq": dense_init(ks[0], D, Hq * (Dh + rr), dtype),
            "w_dkv": dense_init(ks[1], D, r, dtype),
            "w_kr": dense_init(ks[2], D, rr, dtype),
            "w_uk": dense_init(ks[3], r, Hq * Dh, dtype),
            "w_uv": dense_init(ks[4], r, Hq * Dh, dtype),
            "wo": dense_init(ks[5], Hq * Dh, D, dtype),
        }
        return p
    p = {
        "wq": dense_init(ks[0], D, Hq * Dh, dtype),
        "wk": dense_init(ks[1], D, Hkv * Dh, dtype),
        "wv": dense_init(ks[2], D, Hkv * Dh, dtype),
        "wo": dense_init(ks[3], Hq * Dh, D, dtype),
    }
    if s.qk_norm:
        p["q_norm"] = jnp.zeros((Dh,), dtype)
        p["k_norm"] = jnp.zeros((Dh,), dtype)
    return p


# ---------------------------------------------------------------------------
# Projection (+rope, +qk-norm). Returns per-token heads.
# ---------------------------------------------------------------------------

def project_qkv(cfg: ArchConfig, p: Dict, x: jnp.ndarray,
                pos: jnp.ndarray,
                positions3: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: [T, D] -> q [T, Hq, Dh(+rr)], k [T, Hkv, Dh(+rr)], v [T, Hkv, Dh].

    For MLA, ``k`` is the *cache row* [T, 1, r+rr] (latent ‖ rope-key) and
    ``v`` is a zero-width placeholder — the expansion happens in
    :func:`attention_block` via :func:`mla_expand_ctx`.
    """
    s = cfg.spec
    D, Dh, Hq, Hkv = s.d_model, s.head_dim, s.n_heads, s.n_kv_heads
    dt = x.dtype
    if s.kv_lora_rank > 0:
        r, rr = s.kv_lora_rank, s.qk_rope_dim
        q = jnp.einsum("td,dh->th", x, p["wq"].astype(dt))
        q = q.reshape(-1, Hq, Dh + rr)
        q_nope, q_rope = q[..., :Dh], q[..., Dh:]
        q_rope = apply_rope(q_rope, pos, cfg.rope_theta)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        c_kv = jnp.einsum("td,dr->tr", x, p["w_dkv"].astype(dt))
        k_r = jnp.einsum("td,dr->tr", x, p["w_kr"].astype(dt))
        k_r = apply_rope(k_r[:, None, :], pos, cfg.rope_theta)[:, 0, :]
        cache = jnp.concatenate([c_kv, k_r], axis=-1)[:, None, :]  # [T,1,r+rr]
        return q, cache, jnp.zeros((x.shape[0], 1, 0), dt)
    q = jnp.einsum("td,dh->th", x, p["wq"].astype(dt)).reshape(-1, Hq, Dh)
    k = jnp.einsum("td,dh->th", x, p["wk"].astype(dt)).reshape(-1, Hkv, Dh)
    v = jnp.einsum("td,dh->th", x, p["wv"].astype(dt)).reshape(-1, Hkv, Dh)
    if s.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    if cfg.rope_kind == "mrope":
        if positions3 is None:
            positions3 = jnp.stack([pos, pos, pos])
        q = apply_mrope(q, positions3, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions3, cfg.rope_theta, cfg.mrope_sections)
    elif cfg.rope_kind == "rope":
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    return q, k, v


def mla_expand_ctx(cfg: ArchConfig, p: Dict, cache: jnp.ndarray
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expand MLA cache rows [S, 1, r+rr] into per-head K [S, Hq, Dh+rr] and
    V [S, Hq, Dh]. The latent is up-projected; the rope key is shared across
    heads (decoupled MLA rope)."""
    s = cfg.spec
    Dh, Hq, r = s.head_dim, s.n_heads, s.kv_lora_rank
    dt = cache.dtype
    c, k_r = cache[:, 0, :r], cache[:, 0, r:]
    k_nope = jnp.einsum("tr,rh->th", c, p["w_uk"].astype(dt)).reshape(-1, Hq, Dh)
    v = jnp.einsum("tr,rh->th", c, p["w_uv"].astype(dt)).reshape(-1, Hq, Dh)
    k_rope = jnp.broadcast_to(k_r[:, None, :], (k_r.shape[0], Hq, k_r.shape[-1]))
    k = jnp.concatenate([k_nope, k_rope], axis=-1)
    return k, v


# ---------------------------------------------------------------------------
# Full block: project -> policy (comm + context + core) -> output projection.
#
# The *policy* (``attn_fn``) owns everything between projection and the
# output projection: SP collectives (ulysses all-to-all / allgather-KV),
# context-buffer concat + append, and the flash core. This is where
# ``repro.runtime.sp`` splices its distributed variants; the default is
# :func:`local_attention_policy`.
#
# Policy signature:
#   attn_fn(q, k_cur, v_cur, *, seg, pos, ctx_k, ctx_v, ctx_len, causal,
#           window, scale, expand_fn) -> (out [T, Hq, Dv], new_ctx_k,
#                                         new_ctx_v)
# where q/k_cur/v_cur are the LOCAL projected tensors, ctx buffers follow
# the policy's own layout, and expand_fn (MLA) maps cache rows -> (K, V).
# ---------------------------------------------------------------------------


def make_local_attention_policy(flash_impl=None) -> AttnFn:
    """Single-device reference policy (also the oracle for the SP policies).

    ``flash_impl`` defaults to the blocked-jnp flash; tests can pass the
    naive reference or the Pallas kernel.
    """
    from repro.kernels.ref import blocked_flash_attention
    flash = flash_impl or blocked_flash_attention

    def policy(q, k_cur, v_cur, *, seg, pos, ctx_k, ctx_v, ctx_len,
               causal, window, scale, expand_fn=None):
        # MLA ships zero-width v (values live in the latent cache rows);
        # one condition gates both the attend-path concat and the
        # update-path write — mirrored in runtime/sp.py's policies
        has_v = ctx_v is not None and ctx_v.shape[-1] != 0
        if ctx_k is not None:
            C_cap = ctx_k.shape[0]
            kk = jnp.concatenate([ctx_k, k_cur.astype(ctx_k.dtype)], axis=0)
            vv = jnp.concatenate([ctx_v, v_cur.astype(ctx_v.dtype)], axis=0) \
                if has_v else ctx_v
            kv_seg = jnp.concatenate([
                jnp.where(jnp.arange(C_cap) < ctx_len, 0, -1), seg])
            kv_pos = jnp.concatenate([jnp.arange(C_cap, dtype=pos.dtype), pos])
            new_k = jax.lax.dynamic_update_slice_in_dim(
                ctx_k, k_cur.astype(ctx_k.dtype), ctx_len, axis=0)
            new_v = jax.lax.dynamic_update_slice_in_dim(
                ctx_v, v_cur.astype(ctx_v.dtype), ctx_len, axis=0) \
                if has_v else ctx_v
        else:
            kk, vv, kv_seg, kv_pos = k_cur, v_cur, seg, pos
            new_k = new_v = None
        if expand_fn is not None:
            kk, vv = expand_fn(kk)
        out = flash(q, kk, vv, seg, kv_seg, pos, kv_pos,
                    causal=causal, window=window, scale=scale)
        return out, new_k, new_v

    return policy


def attention_block(cfg: ArchConfig, p: Dict, x: jnp.ndarray, *,
                    pos: jnp.ndarray, seg: jnp.ndarray,
                    ctx_k: Optional[jnp.ndarray], ctx_v: Optional[jnp.ndarray],
                    ctx_len: Optional[jnp.ndarray],
                    window: jnp.ndarray | int,
                    attn_fn: AttnFn,
                    positions3: Optional[jnp.ndarray] = None,
                    causal: bool = True
                    ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray],
                               Optional[jnp.ndarray]]:
    """Returns (block_out [T, D], new_ctx_k, new_ctx_v)."""
    with jax.named_scope("attention"):
        s = cfg.spec
        dt = x.dtype
        T = x.shape[0]
        q, k_cur, v_cur = project_qkv(cfg, p, x, pos, positions3)

        expand_fn = None
        scale = 1.0 / math.sqrt(s.head_dim)
        if s.kv_lora_rank > 0:
            scale = 1.0 / math.sqrt(s.head_dim + s.qk_rope_dim)
            expand_fn = functools.partial(mla_expand_ctx, cfg, p)

        out, new_k, new_v = attn_fn(
            q, k_cur, v_cur, seg=seg, pos=pos, ctx_k=ctx_k, ctx_v=ctx_v,
            ctx_len=ctx_len, causal=causal, window=window, scale=scale,
            expand_fn=expand_fn)
        if s.kv_lora_rank > 0:
            out = out[..., :s.head_dim]  # value width (drop rope channels)
        out = out.reshape(T, -1)
        y = jnp.einsum("th,hd->td", out, p["wo"].astype(dt))
        return y, new_k, new_v
