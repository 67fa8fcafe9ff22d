"""Public wrappers around the Pallas kernels.

Each op pads its inputs to kernel tile geometry, runs the Pallas kernel
and unpads. ``fused_cross_entropy`` installs a ``custom_vjp`` wiring the
streaming forward to the streaming d(hidden)/d(W) backward kernels, so the
`[T, V]` logits never exist in any pass.

The kernels compile for the TPU (Mosaic). ``interpret=True`` runs them in
the Pallas interpreter instead — only when the caller asks, as the CPU
tests do; nothing here looks at the backend. The pure-jnp oracles live in
``ref``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .cross_entropy import (cross_entropy_bwd_dh_pallas,
                            cross_entropy_bwd_dw_pallas,
                            cross_entropy_fwd_pallas)
from .flash_attention import flash_attention_pallas
from .mamba_scan import mamba_scan_pallas

__all__ = ["flash_attention", "fused_cross_entropy", "mamba_scan"]


def _pad_to(x: jnp.ndarray, mult: int, axis: int, fill=0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


# ---------------------------------------------------------------------------
# Flash attention.
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, seg_q, seg_kv, pos_q, pos_kv, *,
                    causal: bool = True, window: int = 0,
                    scale: Optional[float] = None,
                    block_q: int = 512, block_kv: int = 512,
                    interpret: bool = False) -> jnp.ndarray:
    """Packed-varlen attention. Padding rows get seg=-2 (matches nothing)."""
    T = q.shape[0]
    S = k.shape[0]
    bq = min(block_q, max(8, T))
    bkv = min(block_kv, max(8, S))
    qp = _pad_to(q, bq, 0)
    kp = _pad_to(k, bkv, 0)
    vp = _pad_to(v, bkv, 0)
    seg_qp = _pad_to(seg_q.astype(jnp.int32), bq, 0, fill=-2)
    seg_kvp = _pad_to(seg_kv.astype(jnp.int32), bkv, 0, fill=-2)
    pos_qp = _pad_to(pos_q.astype(jnp.int32), bq, 0)
    pos_kvp = _pad_to(pos_kv.astype(jnp.int32), bkv, 0)
    out = flash_attention_pallas(
        qp, kp, vp, seg_qp, seg_kvp, pos_qp, pos_kvp,
        causal=causal, window=int(window), scale=scale,
        block_q=bq, block_kv=bkv, interpret=interpret)
    return out[:T]


# ---------------------------------------------------------------------------
# Fused streaming cross entropy (custom_vjp).
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _fused_ce(hidden, w_vocab, targets, valid, block_t, block_v, interpret):
    return _fused_ce_fwd(hidden, w_vocab, targets, valid, block_t, block_v,
                         interpret)[0]


def _fused_ce_fwd(hidden, w_vocab, targets, valid, block_t, block_v,
                  interpret):
    lse, tl = cross_entropy_fwd_pallas(
        hidden, w_vocab, targets, valid,
        block_t=block_t, block_v=block_v, interpret=interpret)
    vf = valid.astype(jnp.float32)
    out = (((lse - tl) * vf).sum(), vf.sum())
    return out, (hidden, w_vocab, targets, valid, lse)


def _fused_ce_bwd(block_t, block_v, interpret, res, g):
    hidden, w_vocab, targets, valid, lse = res
    g_loss, _ = g
    g_rows = jnp.where(valid, g_loss, 0.0).astype(jnp.float32)
    dh = cross_entropy_bwd_dh_pallas(hidden, w_vocab, targets, lse, g_rows,
                                     block_t=block_t, block_v=block_v,
                                     interpret=interpret)
    dw = cross_entropy_bwd_dw_pallas(hidden, w_vocab, targets, lse, g_rows,
                                     block_t=block_t, block_v=block_v,
                                     interpret=interpret)
    return dh, dw, None, None


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def fused_cross_entropy(hidden, w_vocab, targets, valid, *,
                        block_t: int = 256, block_v: int = 1024,
                        interpret: bool = False
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(sum_loss, n_valid) with logits never materialized in any pass."""
    T = hidden.shape[0]
    bt = min(block_t, max(8, T))
    hp = _pad_to(hidden, bt, 0)
    tp = _pad_to(targets.astype(jnp.int32), bt, 0, fill=-1)
    vp = _pad_to(valid, bt, 0, fill=False)
    return _fused_ce(hp, w_vocab, tp, vp, bt, block_v, interpret)


# ---------------------------------------------------------------------------
# Mamba selective scan.
# ---------------------------------------------------------------------------

def mamba_scan(delta, xs, B, C, A, reset, h0, *,
               block_t: int = 256, block_di: int = 512,
               interpret: bool = False
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """y [T, DI], h_last [DI, DS] — see mamba_scan.py for semantics."""
    T = delta.shape[0]
    bt = min(block_t, max(8, T))
    # Padding steps must be state-neutral: delta=0 => a = exp(0*A) = 1 and
    # bx = 0 (identity step), reset=0 so the carried state survives to
    # h_last.
    dp = _pad_to(delta, bt, 0)
    xp = _pad_to(xs, bt, 0)
    Bp = _pad_to(B, bt, 0)
    Cp = _pad_to(C, bt, 0)
    rp = _pad_to(reset.astype(jnp.int32), bt, 0, fill=0)
    y, h_last = mamba_scan_pallas(dp, xp, Bp, Cp, A, rp, h0,
                                  block_t=bt, block_di=block_di,
                                  interpret=interpret)
    return y[:T], h_last
