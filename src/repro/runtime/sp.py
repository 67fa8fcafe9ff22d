"""Sequence-parallel collectives: the two SP attention policies, the
vocab-parallel embed/CE, and the distributed SSM prefix scan.

All functions here run INSIDE ``shard_map`` (manual collectives). Tokens of
a chunk are sharded over the "model" axis in contiguous blocks; the policies
reconstruct whatever global view their algorithm needs:

* ``ulysses``      — Eq. 3's four all-to-alls: tokens gather / heads scatter
                     around the flash core; split-chunk context is stored
                     HEAD-SHARDED (no communication to attend to it).
* ``allgather_kv`` — K/V (or the MLA latent rows — tiny) of the current
                     chunk are all-gathered once; queries stay local; the
                     context buffer is REPLICATED per device (gathered rows
                     are appended), so later slices attend for free. Legal
                     for any head count (DESIGN.md §2.1.3).
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.sp import choose_sp_policy
from repro.kernels.ref import blocked_flash_attention, streaming_ce_stats
from repro.models.config import ArchConfig

__all__ = ["make_ulysses_policy", "make_allgather_kv_policy",
           "sharded_embed", "sharded_ce", "sharded_greedy",
           "make_sp_ssm_scan", "make_sp_conv_tail_exchange",
           "choose_policy", "subgroup_info"]


def choose_policy(cfg: ArchConfig, d_s: int) -> str:
    """Default SP policy at effective degree ``d_s``.

    Delegates to the ONE heuristic in ``repro.core.sp`` — the planner and
    the cost model resolve "auto" through the same function, so the
    runtime can never disagree with what the solver costed
    (tests/test_sp_policy.py pins this)."""
    return choose_sp_policy(cfg.spec, d_s)


def subgroup_info(d_s: int, d_s_eff: int):
    """Sub-group layout for an effective SP degree ``d_s_eff <= d_s``.

    Model-axis device ``m`` holds token shard ``m // r`` where
    ``r = d_s // d_s_eff`` is the replication factor. Returns
    ``(r, sp_groups, replica_groups)``:

    * ``sp_groups[j] = [k*r + j for k in range(d_s_eff)]`` — one device
      per token shard (all with replica index ``j``); every SP collective
      (a2a / KV gather / scan summary) runs with these as its
      ``axis_index_groups``, so the ``r`` replicas never interact;
    * ``replica_groups[s] = [s*r + j for j in range(r)]`` — the ``r``
      devices sharing shard ``s``. They are CONTIGUOUS on the axis, so a
      tiled in-group all_gather of the full-axis batch shards
      reconstructs the sub-group shard's rows in order.

    Both group lists are ``None`` at full degree (``r == 1``); collectives
    then span the whole axis with no group indirection.
    """
    d_s_eff = d_s_eff or d_s
    if d_s % d_s_eff:
        raise ValueError(f"d_s_eff={d_s_eff} must divide d_s={d_s}")
    r = d_s // d_s_eff
    if r == 1:
        return 1, None, None
    sp_groups = [[k * r + j for k in range(d_s_eff)] for j in range(r)]
    replica_groups = [[s * r + j for j in range(r)] for s in range(d_s_eff)]
    return r, sp_groups, replica_groups


# ---------------------------------------------------------------------------
# Attention policies.
# ---------------------------------------------------------------------------

def make_allgather_kv_policy(axis: str, flash=None, *,
                             groups=None) -> Callable:
    """``groups`` (optional ``axis_index_groups``): the SP sub-groups from
    :func:`subgroup_info` when the plan runs at ``d_s_eff < d_s`` — every
    collective here stays inside one sub-group; None spans the axis."""
    flash = flash or blocked_flash_attention

    def policy(q, k_cur, v_cur, *, seg, pos, ctx_k, ctx_v, ctx_len,
               causal, window, scale, expand_fn=None):
        # gather the current chunk's KV rows (or MLA cache rows) + metadata
        with jax.named_scope("sp_collective"):
            k_g, v_g, seg_g, pos_g = (
                jax.lax.all_gather(t, axis, axis=0, tiled=True,
                                   axis_index_groups=groups)
                for t in (k_cur, v_cur, seg, pos))
        # MLA ships zero-width v (values live in the latent cache rows);
        # ONE condition gates both the attend-path concat and the
        # update-path write so the two can never disagree on what counts
        # as "has values"
        has_v = ctx_v is not None and ctx_v.shape[-1] != 0
        if ctx_k is not None:
            C_cap = ctx_k.shape[0]
            kk = jnp.concatenate([ctx_k, k_g.astype(ctx_k.dtype)], axis=0)
            vv = jnp.concatenate([ctx_v, v_g.astype(ctx_v.dtype)], axis=0) \
                if has_v else ctx_v
            kv_seg = jnp.concatenate([
                jnp.where(jnp.arange(C_cap) < ctx_len, 0, -1), seg_g])
            kv_pos = jnp.concatenate(
                [jnp.arange(C_cap, dtype=pos.dtype), pos_g])
            new_k = jax.lax.dynamic_update_slice_in_dim(
                ctx_k, k_g.astype(ctx_k.dtype), ctx_len, axis=0)
            new_v = jax.lax.dynamic_update_slice_in_dim(
                ctx_v, v_g.astype(ctx_v.dtype), ctx_len, axis=0) \
                if has_v else ctx_v
        else:
            kk, vv, kv_seg, kv_pos = k_g, v_g, seg_g, pos_g
            new_k = new_v = None
        if expand_fn is not None:
            kk, vv = expand_fn(kk)
        out = flash(q, kk, vv, seg, kv_seg, pos, kv_pos,
                    causal=causal, window=window, scale=scale)
        return out, new_k, new_v

    return policy


def make_ulysses_policy(axis: str, d_s: int, flash=None, *,
                        groups=None) -> Callable:
    """``d_s`` is the EFFECTIVE degree (the sub-group size when
    ``groups`` — :func:`subgroup_info`'s SP groups — is set)."""
    flash = flash or blocked_flash_attention

    def policy(q, k_cur, v_cur, *, seg, pos, ctx_k, ctx_v, ctx_len,
               causal, window, scale, expand_fn=None):
        assert expand_fn is None, "MLA uses the allgather_kv policy"
        # tokens -> full sequence, heads -> sharded (4 a2a's: q, k, v, out)
        with jax.named_scope("sp_collective"):
            q_g, k_g, v_g = (
                jax.lax.all_to_all(t, axis, split_axis=1, concat_axis=0,
                                   tiled=True, axis_index_groups=groups)
                for t in (q, k_cur, v_cur))
            seg_g, pos_g = (
                jax.lax.all_gather(t, axis, axis=0, tiled=True,
                                   axis_index_groups=groups)
                for t in (seg, pos))
        if ctx_k is not None:
            # context is head-sharded: concat along the sequence dim
            C_cap = ctx_k.shape[0]
            kk = jnp.concatenate([ctx_k, k_g.astype(ctx_k.dtype)], axis=0)
            vv = jnp.concatenate([ctx_v, v_g.astype(ctx_v.dtype)], axis=0)
            kv_seg = jnp.concatenate([
                jnp.where(jnp.arange(C_cap) < ctx_len, 0, -1), seg_g])
            kv_pos = jnp.concatenate(
                [jnp.arange(C_cap, dtype=pos.dtype), pos_g])
            new_k = jax.lax.dynamic_update_slice_in_dim(
                ctx_k, k_g.astype(ctx_k.dtype), ctx_len, axis=0)
            new_v = jax.lax.dynamic_update_slice_in_dim(
                ctx_v, v_g.astype(ctx_v.dtype), ctx_len, axis=0)
        else:
            kk, vv, kv_seg, kv_pos = k_g, v_g, seg_g, pos_g
            new_k = new_v = None
        out_g = flash(q_g, kk, vv, seg_g, kv_seg, pos_g, kv_pos,
                      causal=causal, window=window, scale=scale)
        with jax.named_scope("sp_collective"):
            out = jax.lax.all_to_all(out_g, axis, split_axis=0,
                                     concat_axis=1, tiled=True,
                                     axis_index_groups=groups)
        return out, new_k, new_v

    return policy


# ---------------------------------------------------------------------------
# Vocab-parallel embedding + cross entropy.
# ---------------------------------------------------------------------------

def sharded_embed(embed_local: jnp.ndarray, tokens: jnp.ndarray, axis: str,
                  compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    """Vocab-parallel embedding for token-sharded inputs.

    embed_local: [V/d_s, D] (this device's vocab rows); tokens: [cap_loc]
    (this device's token block). The ids are all-gathered (tiny), every
    device looks the full chunk up in its vocab shard, and the partial rows
    reduce-scatter back to token shards — one collective each way.
    """
    with jax.named_scope("embed"):
        ids = jax.lax.all_gather(tokens, axis, axis=0, tiled=True)  # [cap]
        vs = embed_local.shape[0]
        off = jax.lax.axis_index(axis) * vs
        loc = ids - off
        ok = (loc >= 0) & (loc < vs)
        rows = embed_local[jnp.clip(loc, 0, vs - 1)].astype(compute_dtype)
        rows = jnp.where(ok[:, None], rows, 0)
        return jax.lax.psum_scatter(rows, axis, scatter_dimension=0,
                                    tiled=True)


def sharded_ce(hidden_local: jnp.ndarray, w_local: jnp.ndarray,
               targets_local: jnp.ndarray, valid_local: jnp.ndarray,
               axis: str, vocab_true: Optional[int] = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Vocab-parallel streaming CE (logits never materialized).

    hidden/targets/valid are token-sharded over ``axis``; w_local is the
    vocab shard (possibly padded — ``vocab_true`` masks padded rows). The
    hidden rows are all-gathered (Megatron-style vocab-parallel head);
    per-token stats merge with a distributed LSE.
    Returns (sum_loss, n_valid) REPLICATED across ``axis``.
    """
    with jax.named_scope("sp_collective"):
        h_g, t_g, v_g = (
            jax.lax.all_gather(t, axis, axis=0, tiled=True)
            for t in (hidden_local, targets_local, valid_local))
    vs = w_local.shape[0]
    off = jax.lax.axis_index(axis) * vs
    m, l, tgt = streaming_ce_stats(h_g, w_local, t_g - off,
                                   global_offset=off,
                                   vocab_true=vocab_true)
    # the max-shift is pure numerics: logsumexp is shift-invariant, so a
    # stop_gradient keeps the backward exact (and pmax has no grad rule).
    with jax.named_scope("sp_collective"):
        m_g = jax.lax.stop_gradient(
            jax.lax.pmax(jax.lax.stop_gradient(m), axis))
        l_g = jax.lax.psum(l * jnp.exp(m - m_g), axis)
        tgt_g = jax.lax.psum(tgt, axis)
    lse = m_g + jnp.log(jnp.maximum(l_g, 1e-30))
    loss = jnp.where(v_g, lse - tgt_g, 0.0)
    return loss.sum(), v_g.astype(jnp.float32).sum()


def sharded_greedy(hidden_local: jnp.ndarray, w_local: jnp.ndarray,
                   axis: str, vocab_true: Optional[int] = None,
                   block_v: int = 2048) -> jnp.ndarray:
    """Vocab-parallel greedy sampling: argmax over the full vocabulary
    without materializing logits. Returns int32 ids for LOCAL tokens."""
    T, D = hidden_local.shape
    vs = w_local.shape[0]
    off = jax.lax.axis_index(axis) * vs
    v_hi = vs if vocab_true is None else vocab_true
    pad = (-vs) % block_v
    w = w_local
    if pad:
        w = jnp.concatenate([w, jnp.zeros((pad, D), w.dtype)])
    nb = w.shape[0] // block_v
    wb = w.reshape(nb, block_v, D)

    def body(carry, inp):
        best_v, best_i = carry
        wt, bidx = inp
        # logits in f32 via accumulation dtype, operands stay bf16
        logits = jnp.einsum("td,vd->tv", hidden_local, wt,
                            preferred_element_type=jnp.float32)
        ids = bidx * block_v + jnp.arange(block_v)
        live = (ids[None, :] < vs) & ((off + ids)[None, :] < v_hi)
        logits = jnp.where(live, logits, -jnp.inf)
        m = logits.max(axis=1)
        am = jnp.argmax(logits, axis=1).astype(jnp.int32) + bidx * block_v
        take = m > best_v
        return (jnp.where(take, m, best_v),
                jnp.where(take, am, best_i)), None

    v0 = jnp.full((T,), -jnp.inf, jnp.float32)
    i0 = jnp.zeros((T,), jnp.int32)
    (val, idx), _ = jax.lax.scan(body, (v0, i0), (wb, jnp.arange(nb)))
    gid = idx + off
    gmax = jax.lax.pmax(val, axis)
    cand = jnp.where(val >= gmax, gid, jnp.int32(2 ** 30))
    return jax.lax.pmin(cand, axis).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Distributed SSM: sequence-parallel prefix scan + conv halo exchange.
# ---------------------------------------------------------------------------

def make_sp_ssm_scan(axis: str, d_s: int, local_scan, *,
                     groups=None, rep: int = 1) -> Callable:
    """Wrap a local scan (a, bx, h0) -> (hs, h_last) into a cross-shard
    prefix scan over token shards laid out contiguously along ``axis``.

    Associativity of h_t = a_t h_{t-1} + b_t gives per-shard summaries
    (A_prod, h_last0) with h_last0 the last state when starting from zero.
    The exclusive prefix over shards (tiny [d_s, di, ds] elementwise chain)
    produces each shard's true h0; the local scan is re-run with it
    (recompute beats materializing per-token cumulative products).

    ``d_s`` is the EFFECTIVE shard count; with sub-groups
    (``groups``/``rep`` from :func:`subgroup_info`) the summary gather
    stays inside one SP group and device ``m`` holds shard ``m // rep``.
    """

    def scan(a, bx, h0):
        zeros = jnp.zeros_like(h0)
        _, h_last0 = local_scan(a, bx, zeros)
        a_prod = jnp.prod(a, axis=0)  # elementwise — resets (a=0) propagate
        with jax.named_scope("sp_collective"):
            summ = jax.lax.all_gather(
                jnp.stack([a_prod, h_last0]), axis,
                axis_index_groups=groups)                # [d_s, 2, di, ds]
        my = jax.lax.axis_index(axis) // rep

        def fold(carry, i):
            # carry = state entering shard i (starting from global h0)
            ap, hl = summ[i, 0], summ[i, 1]
            nxt = ap * carry + hl
            return nxt, carry

        _, entering = jax.lax.scan(fold, h0, jnp.arange(d_s))
        my_h0 = entering[my]
        hs, h_last = local_scan(a, bx, my_h0)
        # global final state = state leaving the last shard
        a_all = summ[:, 0]
        h_all = summ[:, 1]
        def fold2(carry, i):
            return a_all[i] * carry + h_all[i], None
        gfinal, _ = jax.lax.scan(fold2, h0, jnp.arange(d_s))
        return hs, gfinal

    return scan


def make_sp_conv_tail_exchange(axis: str, d_s: int, *,
                               rep: int = 1) -> Callable:
    """Conv halo: shard i's causal-conv tail is shard i-1's trailing rows.

    Shard 0 continues from the PREVIOUS CHUNK, whose globally-last tokens
    live on the LAST rank — so the carried tail is ppermuted (d_s-1 -> 0).
    Each rank stores its own trailing rows after the chunk (ssm.mamba_apply),
    which makes this exchange self-consistent across consecutive split
    chunks.

    ``d_s`` is the EFFECTIVE shard count; ``rep > 1`` replays the same
    ring inside each of the ``rep`` SP sub-groups (device ``k*rep + j``
    is shard ``k`` of group ``j`` — :func:`subgroup_info`'s layout).
    """
    # ppermute takes explicit (src, dst) device pairs, so the sub-group
    # structure is baked into the permutation rather than group lists
    shift = [(k * rep + j, (k + 1) * rep + j)
             for j in range(rep) for k in range(d_s - 1)]
    wrap = [((d_s - 1) * rep + j, j) for j in range(rep)]

    def exchange(xs: jnp.ndarray, carried_tail: jnp.ndarray) -> jnp.ndarray:
        K1 = carried_tail.shape[0]
        my_tail = jax.lax.dynamic_slice_in_dim(
            xs, xs.shape[0] - K1, K1, axis=0)
        with jax.named_scope("sp_collective"):
            from_left = jax.lax.ppermute(my_tail, axis, shift)
            prev_chunk = jax.lax.ppermute(carried_tail.astype(xs.dtype),
                                          axis, wrap)
        my = jax.lax.axis_index(axis) // rep
        return jnp.where(my == 0, prev_chunk, from_left)

    return exchange
