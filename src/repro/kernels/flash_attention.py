"""Pallas TPU flash-attention kernel: packed-varlen causal attention with
split-chunk context and sliding-window support.

TPU adaptation of the paper's flash-attn dependency (DESIGN.md §2.1.6):

* grid = (Hq, n_q_blocks, n_kv_blocks) — the kv axis is innermost, which on
  TPU executes sequentially per (head, q-block), so the online-softmax
  running state (m, l, acc) lives in VMEM scratch and persists across kv
  steps; no HBM round-trips for the accumulator.
* BlockSpec tiling: q tile [BQ, Dh], kv tile [BKV, Dh] with Dh padded to a
  multiple of 128 (MXU lane width) by the ops.py wrapper, BQ/BKV multiples
  of 8 (sublane). Default BQ = BKV = 512 keeps the working set
  (q + kv tiles + f32 accumulator ≈ 1.3 MiB at Dh=128) far under the
  ~16 MiB VMEM budget, leaving room for double-buffered input DMA.
* GQA is resolved in the BlockSpec index_map: query head h reads kv head
  ``h // (Hq // Hkv)`` — no KV repetition is materialized.
* the packed-varlen mask (segment equality x causality x window x context
  offsets) is computed in-kernel from [T,1]-shaped seg/pos tiles; fully
  masked kv tiles contribute zeros (the online rescale handles it).

Validated in ``interpret=True`` mode against ``ref.flash_attention_reference``
over shape/dtype sweeps (tests/test_kernels.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas", "DEFAULT_BQ", "DEFAULT_BKV"]

DEFAULT_BQ = 512
DEFAULT_BKV = 512
NEG_INF = -1e30


def _kernel(seg_q_ref, pos_q_ref, seg_kv_ref, pos_kv_ref,
            q_ref, k_ref, v_ref,           # inputs
            o_ref,                          # output
            acc_ref, m_ref, l_ref,          # VMEM scratch
            *, scale: float, causal: bool, window: int, n_kv: int):
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...].astype(jnp.float32)      # [BQ, Dh]
    k = k_ref[...].astype(jnp.float32)      # [BKV, Dh]
    v = v_ref[...].astype(jnp.float32)      # [BKV, Dv]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    seg_q = seg_q_ref[...]                  # [BQ, 1]
    seg_kv = seg_kv_ref[...]                # [BKV, 1]
    pos_q = pos_q_ref[...]
    pos_kv = pos_kv_ref[...]
    mask = (seg_q == seg_kv.T) & (seg_q >= 0)
    if causal:
        mask &= pos_kv.T <= pos_q
    if window > 0:
        mask &= (pos_q - pos_kv.T) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                      # [BQ, 1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                   # [BQ, BKV]
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kv_idx == n_kv - 1)
    def _finish():
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l > 0, l, 1.0)
        o_ref[...] = jnp.where(l > 0, out, 0.0).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, seg_q, seg_kv, pos_q, pos_kv, *,
                           causal: bool = True, window: int = 0,
                           scale: Optional[float] = None,
                           block_q: int = DEFAULT_BQ,
                           block_kv: int = DEFAULT_BKV,
                           interpret: bool = False) -> jnp.ndarray:
    """q: [T, Hq, Dh]; k: [S, Hkv, Dh]; v: [S, Hkv, Dv] -> [T, Hq, Dv].

    Preconditions (enforced by the ops.py wrapper): T % block_q == 0,
    S % block_kv == 0 (after padding), Hq % Hkv == 0, ``window``/``causal``
    static.
    """
    T, Hq, Dh = q.shape
    S, Hkv, Dv = v.shape
    scale = scale if scale is not None else Dh ** -0.5
    bq = min(block_q, T)
    bkv = min(block_kv, S)
    assert T % bq == 0 and S % bkv == 0, (T, bq, S, bkv)
    n_q, n_kv = T // bq, S // bkv
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv

    # head-major layout so each (head, block) is a clean 2D tile
    qh = jnp.swapaxes(q, 0, 1)               # [Hq, T, Dh]
    kh = jnp.swapaxes(k, 0, 1)               # [Hkv, S, Dh]
    vh = jnp.swapaxes(v, 0, 1)               # [Hkv, S, Dv]
    seg_q2 = seg_q.reshape(T, 1).astype(jnp.int32)
    seg_kv2 = seg_kv.reshape(S, 1).astype(jnp.int32)
    pos_q2 = pos_q.reshape(T, 1).astype(jnp.int32)
    pos_kv2 = pos_kv.reshape(S, 1).astype(jnp.int32)

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               window=int(window), n_kv=n_kv)
    out = pl.pallas_call(
        kernel,
        grid=(Hq, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((bq, 1), lambda h, i, j: (i, 0)),         # seg_q
            pl.BlockSpec((bq, 1), lambda h, i, j: (i, 0)),         # pos_q
            pl.BlockSpec((bkv, 1), lambda h, i, j: (j, 0)),        # seg_kv
            pl.BlockSpec((bkv, 1), lambda h, i, j: (j, 0)),        # pos_kv
            pl.BlockSpec((None, bq, Dh), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((None, bkv, Dh),
                         lambda h, i, j: (h // group, j, 0)),
            pl.BlockSpec((None, bkv, Dv),
                         lambda h, i, j: (h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, Dv), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((Hq, T, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, Dv), jnp.float32),   # acc
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running sum
        ],
        interpret=interpret,
    )(seg_q2, pos_q2, seg_kv2, pos_kv2, qh, kh, vh)
    return jnp.swapaxes(out, 0, 1)
