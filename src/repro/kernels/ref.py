"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth for the per-kernel allclose tests and the
reference substrate for small-model equivalence tests. Two attention
variants exist:

* :func:`flash_attention_reference` — naive O(T*S) score materialization;
  bitwise-simple, used as the oracle.
* :func:`blocked_flash_attention` — online-softmax over KV tiles in plain
  jnp (lax.scan) with a FlashAttention-2 ``custom_vjp``: O(T * BLOCK)
  live scores in either pass and O((T + S) * H * D) residuals under
  ``grad``.
  This is the attention core of the training and serving paths.

Packed-varlen mask rule (shared by every implementation):
  attend(qi, kj)  iff  seg_q[i] == seg_kv[j]  and  seg_q[i] >= 0
                  and (not causal or pos_kv[j] <= pos_q[i])
                  and (window <= 0 or pos_q[i] - pos_kv[j] < window)
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["flash_attention_reference", "blocked_flash_attention",
           "cross_entropy_reference", "streaming_cross_entropy",
           "mamba_scan_reference"]

NEG_INF = -1e30


def _mask(seg_q, seg_kv, pos_q, pos_kv, causal, window):
    m = (seg_q[:, None] == seg_kv[None, :]) & (seg_q[:, None] >= 0)
    if causal:
        m &= pos_kv[None, :] <= pos_q[:, None]
    big = jnp.int32(2 ** 30)
    w = jnp.where(window > 0, window, big)
    m &= (pos_q[:, None] - pos_kv[None, :]) < w
    return m


def _expand_kv(k: jnp.ndarray, n_q_heads: int) -> jnp.ndarray:
    """GQA: repeat kv heads to match query heads."""
    Hkv = k.shape[1]
    if Hkv == n_q_heads:
        return k
    rep = n_q_heads // Hkv
    return jnp.repeat(k, rep, axis=1)


def flash_attention_reference(q, k, v, seg_q, seg_kv, pos_q, pos_kv, *,
                              causal: bool = True, window=0,
                              scale: Optional[float] = None) -> jnp.ndarray:
    """q: [T, Hq, Dh]; k/v: [S, Hkv, Dh(v may differ)] -> [T, Hq, Dv]."""
    Hq = q.shape[1]
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    k = _expand_kv(k, Hq)
    v = _expand_kv(v, Hq)
    s = jnp.einsum("thd,shd->hts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    m = _mask(seg_q, seg_kv, pos_q, pos_kv, causal, jnp.asarray(window))
    s = jnp.where(m[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows (padding) produce uniform p; zero them for hygiene
    any_valid = m.any(axis=-1)
    p = jnp.where(any_valid[None, :, None], p, 0.0)
    out = jnp.einsum("hts,shd->thd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def blocked_flash_attention(q, k, v, seg_q, seg_kv, pos_q, pos_kv, *,
                            causal: bool = True, window=0,
                            scale: Optional[float] = None,
                            block_kv: int = 512) -> jnp.ndarray:
    """Online-softmax over KV tiles with a FlashAttention-2 backward.

    The forward keeps O(T * block_kv) scores live; under ``grad`` only q,
    k, v (at their own ``Hkv`` heads: GQA expands each tile inside the
    scan), the output and the per-row log-sum-exp are saved, and the
    backward re-scans the KV tiles recomputing each tile's probabilities —
    residuals are O((T + S) * H * D), never O(T * S). Rows that attend to
    no key (padding) return zeros."""
    T, Hq, Dh = q.shape
    S, Hkv = k.shape[:2]
    Dv = v.shape[-1]
    scale = scale if scale is not None else Dh ** -0.5
    pad = (-S) % block_kv
    if pad:
        k = jnp.concatenate([k, jnp.zeros((pad, *k.shape[1:]), k.dtype)])
        v = jnp.concatenate([v, jnp.zeros((pad, *v.shape[1:]), v.dtype)])
        seg_kv = jnp.concatenate([seg_kv, jnp.full((pad,), -2, seg_kv.dtype)])
        pos_kv = jnp.concatenate([pos_kv, jnp.zeros((pad,), pos_kv.dtype)])
    nb = k.shape[0] // block_kv
    kb = k.reshape(nb, block_kv, Hkv, Dh)
    vb = v.reshape(nb, block_kv, Hkv, Dv)
    sb = seg_kv.reshape(nb, block_kv)
    pb = pos_kv.reshape(nb, block_kv)
    return _flash(q, kb, vb, seg_q, sb, pos_q, pb, jnp.asarray(window),
                  causal, scale)


def _tile_scores(q, kk, seg_q, sseg, pos_q, ppos, window, causal, scale):
    """Scaled scores [Hq, T, block] of one KV tile (at its query-head
    expansion) and its mask [1, T, block]. QK runs in the input dtype with
    f32 accumulation: bf16 products are exact in f32, and the matmul reads
    half the HBM of upcast inputs."""
    s = jnp.einsum("thd,shd->hts", q, kk,
                   preferred_element_type=jnp.float32) * scale
    return s, _mask(seg_q, sseg, pos_q, ppos, causal, window)[None]


def _flash_fwd_scan(q, kb, vb, seg_q, sb, pos_q, pb, window, causal, scale):
    """Returns (out [T, Hq, Dv] in q.dtype, lse [Hq, T] f32)."""
    T, Hq, _ = q.shape
    Dv = vb.shape[-1]

    def body(carry, blk):
        acc, m_run, l_run = carry
        kk, vv, sseg, ppos = blk
        kk, vv = _expand_kv(kk, Hq), _expand_kv(vv, Hq)
        s, msk = _tile_scores(q, kk, seg_q, sseg, pos_q, ppos, window,
                              causal, scale)
        m_new = jnp.maximum(m_run, jnp.where(msk, s, NEG_INF).max(axis=-1))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.where(msk, jnp.exp(s - m_new[..., None]), 0.0)
        l_new = l_run * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "hts,shd->htd", p, vv.astype(jnp.float32))
        return (acc, m_new, l_new), None

    acc0 = jnp.zeros((Hq, T, Dv), jnp.float32)
    m0 = jnp.full((Hq, T), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Hq, T), jnp.float32)
    (acc, m_run, l_run), _ = jax.lax.scan(body, (acc0, m0, l0),
                                          (kb, vb, sb, pb))
    out = acc / jnp.maximum(l_run[..., None], 1e-30)
    out = jnp.where(l_run[..., None] > 0, out, 0.0)
    lse = m_run + jnp.log(jnp.maximum(l_run, 1e-30))
    return jnp.transpose(out, (1, 0, 2)).astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _flash(q, kb, vb, seg_q, sb, pos_q, pb, window, causal, scale):
    return _flash_fwd_scan(q, kb, vb, seg_q, sb, pos_q, pb, window,
                           causal, scale)[0]


def _flash_fwd(q, kb, vb, seg_q, sb, pos_q, pb, window, causal, scale):
    out, lse = _flash_fwd_scan(q, kb, vb, seg_q, sb, pos_q, pb, window,
                               causal, scale)
    return out, (q, kb, vb, seg_q, sb, pos_q, pb, window, out, lse)


def _flash_bwd(causal, scale, res, do):
    q, kb, vb, seg_q, sb, pos_q, pb, window, out, lse = res
    dt = q.dtype
    Hq, Hkv = q.shape[1], kb.shape[2]
    do = do.astype(dt)

    def fold_heads(g):
        """Transpose of the GQA expansion: sum each kv head's query group."""
        return g.reshape(g.shape[0], Hkv, Hq // Hkv, g.shape[-1]).sum(2)
    # D_i = rowsum(dO * O): the softmax-Jacobian term of every tile
    di = jnp.einsum("thd,thd->ht", do, out.astype(dt),
                    preferred_element_type=jnp.float32)

    def body(dq, blk):
        kk, vv, sseg, ppos = blk
        kk, vv = _expand_kv(kk, Hq), _expand_kv(vv, Hq)
        s, msk = _tile_scores(q, kk, seg_q, sseg, pos_q, ppos, window,
                              causal, scale)
        p = jnp.where(msk, jnp.exp(s - lse[..., None]), 0.0)
        dv = jnp.einsum("hts,thd->shd", p.astype(dt), do,
                        preferred_element_type=jnp.float32)
        dp = jnp.einsum("thd,shd->hts", do, vv.astype(dt),
                        preferred_element_type=jnp.float32)
        ds = (p * (dp - di[..., None])).astype(dt)
        dq = dq + jnp.einsum("hts,shd->thd", ds, kk.astype(dt),
                             preferred_element_type=jnp.float32) * scale
        dk = jnp.einsum("hts,thd->shd", ds, q,
                        preferred_element_type=jnp.float32) * scale
        return dq, (fold_heads(dk).astype(kb.dtype),
                    fold_heads(dv).astype(vb.dtype))

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dq, (dk, dv) = jax.lax.scan(body, dq0, (kb, vb, sb, pb))
    return (dq.astype(dt), dk, dv, None, None, None, None, None)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Cross entropy.
# ---------------------------------------------------------------------------

def cross_entropy_reference(hidden, w_vocab, targets, valid
                            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Naive full-logits CE. hidden: [T, D]; w_vocab: [V, D]; targets: [T];
    valid: [T] bool. Returns (sum_loss fp32 scalar, n_valid fp32 scalar)."""
    logits = jnp.einsum("td,vd->tv", hidden.astype(jnp.float32),
                        w_vocab.astype(jnp.float32))
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(
        logits, targets[:, None].astype(jnp.int32), axis=-1)[:, 0]
    loss = jnp.where(valid, lse - tgt, 0.0)
    return loss.sum(), valid.astype(jnp.float32).sum()


def streaming_cross_entropy(hidden, w_vocab, targets, valid, *,
                            block_v: int = 2048
                            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Vocab-tiled online-logsumexp CE: never materializes [T, V].

    Matches cross_entropy_reference exactly (up to fp reassociation); jnp
    analogue of the Pallas streaming kernel; differentiable (the backward
    re-derives each vocab tile's logits through the remat'd scan body).
    """
    T, D = hidden.shape
    V = w_vocab.shape[0]
    pad = (-V) % block_v
    if pad:
        w_vocab = jnp.concatenate(
            [w_vocab, jnp.zeros((pad, D), w_vocab.dtype)])
    nb = w_vocab.shape[0] // block_v
    wb = w_vocab.reshape(nb, block_v, D)
    tgt = targets.astype(jnp.int32)

    def body(carry, inp):
        m_run, l_run, t_run = carry
        w, bidx = inp
        # logits in f32 via accumulation dtype, operands stay bf16
        logits = jnp.einsum("td,vd->tv", hidden, w,
                            preferred_element_type=jnp.float32)
        vocab_ids = bidx * block_v + jnp.arange(block_v)
        live = vocab_ids[None, :] < V
        logits = jnp.where(live, logits, NEG_INF)
        m_new = jnp.maximum(m_run, logits.max(axis=-1))
        l_new = l_run * jnp.exp(m_run - m_new) + \
            jnp.exp(logits - m_new[:, None]).sum(axis=-1)
        hit = vocab_ids[None, :] == tgt[:, None]
        t_new = t_run + jnp.where(hit, logits, 0.0).sum(axis=-1)
        return (m_new, l_new, t_new), None

    m0 = jnp.full((T,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((T,), jnp.float32)
    t0 = jnp.zeros((T,), jnp.float32)
    # the remat'd body saves only the [T] carries per vocab tile; its
    # backward recomputes the tile's logits instead of keeping [T, V]
    (m_run, l_run, t_run), _ = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False), (m0, l0, t0),
        (wb, jnp.arange(nb)))
    lse = m_run + jnp.log(jnp.maximum(l_run, 1e-30))
    loss = jnp.where(valid, lse - t_run, 0.0)
    return loss.sum(), valid.astype(jnp.float32).sum()


def streaming_ce_stats(hidden, w_shard, local_targets, *,
                       block_v: int = 2048,
                       global_offset=0,
                       vocab_true: Optional[int] = None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-token softmax stats against a vocab SHARD: returns (m, l, tgt)
    where m = max logit, l = sum exp(logit - m), tgt = target logit if the
    target id falls inside this shard else 0. ``local_targets`` are already
    offset into shard-local ids (out-of-range => not in shard).

    ``global_offset``/``vocab_true`` mask executor-side vocab padding
    (Megatron-style: V is padded to a multiple of d_s; padded rows must not
    contaminate the logsumexp).

    The vocab-parallel CE merge (runtime/sp.py) combines shards with
      m_g = pmax(m); l_g = psum(l * exp(m - m_g)); tgt_g = psum(tgt).
    """
    T, D = hidden.shape
    Vs = w_shard.shape[0]
    pad = (-Vs) % block_v
    if pad:
        w_shard = jnp.concatenate([w_shard, jnp.zeros((pad, D), w_shard.dtype)])
    nb = w_shard.shape[0] // block_v
    wb = w_shard.reshape(nb, block_v, D)
    tgt_ids = local_targets.astype(jnp.int32)
    v_hi = Vs if vocab_true is None else vocab_true

    def body(carry, inp):
        m_run, l_run, t_run = carry
        w, bidx = inp
        # logits in f32 via accumulation dtype, operands stay bf16
        logits = jnp.einsum("td,vd->tv", hidden, w,
                            preferred_element_type=jnp.float32)
        ids = bidx * block_v + jnp.arange(block_v)
        live = (ids[None, :] < Vs) & \
            ((global_offset + ids)[None, :] < v_hi)
        logits = jnp.where(live, logits, NEG_INF)
        m_new = jnp.maximum(m_run, logits.max(axis=-1))
        l_new = l_run * jnp.exp(m_run - m_new) + \
            jnp.exp(logits - m_new[:, None]).sum(axis=-1)
        # dead (padded) rows must not match: a local target id from another
        # shard can collide with a padded row index here.
        hit = (ids[None, :] == tgt_ids[:, None]) & live
        t_new = t_run + jnp.where(hit, logits, 0.0).sum(axis=-1)
        return (m_new, l_new, t_new), None

    m0 = jnp.full((T,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((T,), jnp.float32)
    t0 = jnp.zeros((T,), jnp.float32)
    # remat'd body: [T] carries per vocab tile are the only residuals
    (m, l, t), _ = jax.lax.scan(jax.checkpoint(body, prevent_cse=False),
                                (m0, l0, t0), (wb, jnp.arange(nb)))
    return m, l, t


# ---------------------------------------------------------------------------
# Mamba selective scan (oracle: straight sequential scan).
# ---------------------------------------------------------------------------

def mamba_scan_reference(a, bx, h0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """h_t = a_t * h_{t-1} + bx_t, sequential. a/bx: [T, di, ds]."""
    def step(h, inp):
        at, bt = inp
        h = at * h + bt
        return h, h
    h_last, hs = jax.lax.scan(step, h0, (a, bx))
    return hs, h_last
