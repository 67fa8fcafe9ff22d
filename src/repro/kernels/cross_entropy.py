"""Pallas TPU fused streaming cross-entropy.

The paper (§IV) adopts Megatron's fused *in-place* CE to stop logits
intermediates from blowing up the peak at the start of backward. On TPU we
go one step further (beyond-paper, see DESIGN.md §2.1): the `[T, V]` logits
are never materialized at all —

* **forward** (`cross_entropy_fwd_pallas`): grid (n_token_blocks,
  n_vocab_blocks), vocab innermost/sequential; each step computes the
  `[BT, BV]` logits tile on the MXU and folds it into running
  (max, sumexp, target-logit) VMEM scratch; emits per-token (lse, tgt).
* **backward** (`cross_entropy_bwd_*`): recomputes the logits tile, forms
  `p - onehot` in VMEM and immediately contracts it — into `[BT, D]` for
  d(hidden) (vocab-sequential accumulation) and `[BV, D]` for d(W)
  (token-sequential accumulation). Peak live memory is O(BT*BV + BT*D).

Tiles: BT=256 tokens x BV vocab rows, BV=1024 shrunk with D until an f32
[BV, D] tile fits 2 MiB (BV=128 at D=3072), so every kernel stays inside
the default 16 MiB of scoped VMEM.

ops.py wires these into a custom_vjp; the pure-jnp oracle is
``ref.streaming_cross_entropy`` / ``ref.cross_entropy_reference``.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["cross_entropy_fwd_pallas", "cross_entropy_bwd_dh_pallas",
           "cross_entropy_bwd_dw_pallas", "DEFAULT_BT", "DEFAULT_BV"]

DEFAULT_BT = 256
DEFAULT_BV = 1024
NEG_INF = -1e30
# budget for one f32 [BV, D] vocab tile: the double-buffered tiles, the
# logits and the dW accumulator must share the 16 MiB of scoped VMEM a
# Mosaic kernel gets by default
VOCAB_TILE_BYTES = 2 * 2 ** 20


def _fit_block_v(block_v: int, V: int, D: int) -> int:
    """``block_v`` shrunk (to a multiple of 128, at least 128) until one f32
    [block_v, D] tile fits ``VOCAB_TILE_BYTES``; at most ``V``."""
    rows = max(128, VOCAB_TILE_BYTES // (4 * D) // 128 * 128)
    return min(block_v, rows, V)


def _dot(a, b, dims):
    """MXU dot with f32 accumulation, operands in their own dtype (bf16
    products are exact in f32). The precision is pinned: Mosaic rejects a
    bf16 operand at the f32 contract precision that a process-wide
    ``jax_default_matmul_precision="highest"`` would request."""
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=jnp.float32)


def _tile_logits(h_ref, w_ref):
    """[BT, BV] f32 logits of one (token, vocab) tile."""
    return _dot(h_ref[...], w_ref[...], ((1,), (1,)))


def _fwd_kernel(h_ref, w_ref, tgt_ref, valid_ref,
                lse_ref, tl_ref,
                m_ref, l_ref, t_ref,
                *, n_v: int, bv: int, vocab: int):
    v_idx = pl.program_id(1)

    @pl.when(v_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        t_ref[...] = jnp.zeros_like(t_ref)

    logits = _tile_logits(h_ref, w_ref)         # [BT, BV] f32
    vocab_ids = v_idx * bv + jax.lax.broadcasted_iota(
        jnp.int32, (1, bv), dimension=1)        # [1, BV]
    live = vocab_ids < vocab
    logits = jnp.where(live, logits, NEG_INF)

    m_prev = m_ref[...]                          # [BT, 1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(
        jnp.exp(logits - m_new), axis=1, keepdims=True)
    m_ref[...] = m_new
    hit = vocab_ids == tgt_ref[...]              # [BT, BV] via broadcast
    t_ref[...] += jnp.sum(jnp.where(hit, logits, 0.0), axis=1, keepdims=True)

    @pl.when(v_idx == n_v - 1)
    def _finish():
        lse = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))
        lse_ref[...] = lse
        tl_ref[...] = t_ref[...]


def cross_entropy_fwd_pallas(hidden, w_vocab, targets, valid, *,
                             block_t: int = DEFAULT_BT,
                             block_v: int = DEFAULT_BV,
                             interpret: bool = False
                             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """hidden [T, D], w_vocab [V, D], targets [T] int32, valid [T] bool ->
    (lse [T], tgt_logit [T]) fp32. T and V are padded by the caller."""
    T, D = hidden.shape
    V = w_vocab.shape[0]
    bt = min(block_t, T)
    bv = _fit_block_v(block_v, V, D)
    assert T % bt == 0
    padV = (-V) % bv
    if padV:
        w_vocab = jnp.concatenate(
            [w_vocab, jnp.zeros((padV, D), w_vocab.dtype)])
    n_t, n_v = T // bt, w_vocab.shape[0] // bv
    tgt2 = targets.reshape(T, 1).astype(jnp.int32)
    valid2 = valid.reshape(T, 1).astype(jnp.int32)

    kernel = functools.partial(_fwd_kernel, n_v=n_v, bv=bv, vocab=V)
    lse, tl = pl.pallas_call(
        kernel,
        grid=(n_t, n_v),
        in_specs=[
            pl.BlockSpec((bt, D), lambda i, j: (i, 0)),      # hidden
            pl.BlockSpec((bv, D), lambda i, j: (j, 0)),      # w tile
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),      # targets
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),      # valid
        ],
        out_specs=[
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, 1), jnp.float32),
            jax.ShapeDtypeStruct((T, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bt, 1), jnp.float32),
            pltpu.VMEM((bt, 1), jnp.float32),
            pltpu.VMEM((bt, 1), jnp.float32),
        ],
        interpret=interpret,
    )(hidden, w_vocab, tgt2, valid2)
    return lse[:, 0], tl[:, 0]


# ---------------------------------------------------------------------------
# Backward.
# ---------------------------------------------------------------------------

def _bwd_dh_kernel(h_ref, w_ref, tgt_ref, lse_ref, g_ref,
                   dh_ref, acc_ref,
                   *, n_v: int, bv: int, vocab: int):
    v_idx = pl.program_id(1)

    @pl.when(v_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...]
    logits = _tile_logits(h_ref, w_ref)
    vocab_ids = v_idx * bv + jax.lax.broadcasted_iota(
        jnp.int32, (1, bv), dimension=1)
    live = vocab_ids < vocab
    p = jnp.where(live, jnp.exp(logits - lse_ref[...]), 0.0)  # [BT, BV]
    hit = vocab_ids == tgt_ref[...]
    coef = (p - jnp.where(hit, 1.0, 0.0)) * g_ref[...]        # [BT, BV]
    acc_ref[...] += _dot(coef.astype(w.dtype), w, ((1,), (0,)))

    @pl.when(v_idx == n_v - 1)
    def _finish():
        dh_ref[...] = acc_ref[...].astype(dh_ref.dtype)


def cross_entropy_bwd_dh_pallas(hidden, w_vocab, targets, lse, g_rows, *,
                                block_t: int = DEFAULT_BT,
                                block_v: int = DEFAULT_BV,
                                interpret: bool = False) -> jnp.ndarray:
    """d(hidden): [T, D]. ``g_rows`` [T] = upstream grad * valid mask."""
    T, D = hidden.shape
    V = w_vocab.shape[0]
    bt = min(block_t, T)
    bv = _fit_block_v(block_v, V, D)
    padV = (-V) % bv
    if padV:
        w_vocab = jnp.concatenate(
            [w_vocab, jnp.zeros((padV, D), w_vocab.dtype)])
    n_t, n_v = T // bt, w_vocab.shape[0] // bv
    kernel = functools.partial(_bwd_dh_kernel, n_v=n_v, bv=bv, vocab=V)
    return pl.pallas_call(
        kernel,
        grid=(n_t, n_v),
        in_specs=[
            pl.BlockSpec((bt, D), lambda i, j: (i, 0)),
            pl.BlockSpec((bv, D), lambda i, j: (j, 0)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bt, D), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, D), hidden.dtype),
        scratch_shapes=[pltpu.VMEM((bt, D), jnp.float32)],
        interpret=interpret,
    )(hidden, w_vocab, targets.reshape(T, 1).astype(jnp.int32),
      lse.reshape(T, 1).astype(jnp.float32),
      g_rows.reshape(T, 1).astype(jnp.float32))


def _bwd_dw_kernel(h_ref, w_ref, tgt_ref, lse_ref, g_ref,
                   dw_ref, acc_ref,
                   *, n_t: int, bv: int, vocab: int):
    t_idx = pl.program_id(1)

    @pl.when(t_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    h = h_ref[...]                              # [BT, D]
    v_idx = pl.program_id(0)
    logits = _tile_logits(h_ref, w_ref)
    vocab_ids = v_idx * bv + jax.lax.broadcasted_iota(
        jnp.int32, (1, bv), dimension=1)
    live = vocab_ids < vocab
    p = jnp.where(live, jnp.exp(logits - lse_ref[...]), 0.0)
    hit = vocab_ids == tgt_ref[...]
    coef = (p - jnp.where(hit, 1.0, 0.0)) * g_ref[...]        # [BT, BV]
    acc_ref[...] += _dot(coef.astype(h.dtype), h, ((0,), (0,)))  # [BV, D]

    @pl.when(t_idx == n_t - 1)
    def _finish():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def cross_entropy_bwd_dw_pallas(hidden, w_vocab, targets, lse, g_rows, *,
                                block_t: int = DEFAULT_BT,
                                block_v: int = DEFAULT_BV,
                                interpret: bool = False) -> jnp.ndarray:
    """d(w_vocab): [V, D]."""
    T, D = hidden.shape
    V = w_vocab.shape[0]
    bt = min(block_t, T)
    bv = _fit_block_v(block_v, V, D)
    padV = (-V) % bv
    w_pad = w_vocab
    if padV:
        w_pad = jnp.concatenate([w_vocab, jnp.zeros((padV, D), w_vocab.dtype)])
    n_t, n_v = T // bt, w_pad.shape[0] // bv
    kernel = functools.partial(_bwd_dw_kernel, n_t=n_t, bv=bv, vocab=V)
    dw = pl.pallas_call(
        kernel,
        grid=(n_v, n_t),
        in_specs=[
            pl.BlockSpec((bt, D), lambda j, i: (i, 0)),
            pl.BlockSpec((bv, D), lambda j, i: (j, 0)),
            pl.BlockSpec((bt, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((bt, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((bt, 1), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bv, D), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((w_pad.shape[0], D), w_vocab.dtype),
        scratch_shapes=[pltpu.VMEM((bv, D), jnp.float32)],
        interpret=interpret,
    )(hidden, w_pad, targets.reshape(T, 1).astype(jnp.int32),
      lse.reshape(T, 1).astype(jnp.float32),
      g_rows.reshape(T, 1).astype(jnp.float32))
    return dw[:V]
