"""Pallas TPU selective-scan (mamba-1) kernel.

Grid = (n_channel_blocks, n_time_blocks): channel blocks are independent
(parallel); the time axis is innermost/sequential, carrying the recurrent
state ``h [B_DI, DS]`` in VMEM scratch across time blocks. Within a block
the recurrence is stepped with a ``fori_loop`` over VMEM rows — the op is
VPU-bound elementwise work (no MXU), so the loop costs what the math costs;
what matters is that delta/B/C/x tiles stream HBM->VMEM once and the state
never leaves VMEM.

Inputs are the raw per-token SSM tensors (the [T, di, ds] outer products are
formed *inside* the kernel tile-by-tile and never hit HBM):
    delta [T, DI], xs [T, DI], B [T, DS], C [T, DS], A [DI, DS],
    reset [T, 1] (1 => sequence start: kills the recurrence),
    h0 [DI, DS] (split-chunk carry-in).
Outputs: y [T, DI] (pre-gating), h_last [DI, DS].

Oracle: ``ref.mamba_scan_reference`` composed with the same outer products
(tests/test_kernels.py sweeps shapes and dtypes in interpret mode).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["mamba_scan_pallas", "DEFAULT_BT", "DEFAULT_BDI"]

DEFAULT_BT = 256
DEFAULT_BDI = 512


def _kernel(delta_ref, xs_ref, b_ref, c_ref, a_ref, reset_ref, h0_ref,
            y_ref, hlast_ref,
            h_ref,
            *, n_t: int, bt: int):
    t_idx = pl.program_id(1)

    @pl.when(t_idx == 0)
    def _init():
        h_ref[...] = h0_ref[...].astype(jnp.float32)

    a_mat = a_ref[...].astype(jnp.float32)          # [BDI, DS]
    f32 = jnp.float32
    # rows are read from the refs in sublane-aligned groups (Mosaic cannot
    # load one bf16 row at an unaligned dynamic offset); the group's rows
    # then step the recurrence with static slices
    grp = 16 if bt % 16 == 0 else bt

    def group(gi, h):
        rows = pl.ds(pl.multiple_of(gi * grp, grp), grp)
        d_g = delta_ref[rows, :].astype(f32)                   # [G, BDI]
        x_g = xs_ref[rows, :].astype(f32)
        b_g = b_ref[rows, :].astype(f32)                       # [G, DS]
        c_g = c_ref[rows, :].astype(f32)
        # reset kills the carried state: keep = 0 at a sequence start,
        # broadcast along lanes only (Mosaic broadcasts one axis at a time)
        keep_g = jnp.broadcast_to(
            jnp.where(reset_ref[rows, :] > 0, 0.0, 1.0), d_g.shape)
        ys = []
        for i in range(grp):
            d_t, x_t = d_g[i:i + 1], x_g[i:i + 1]              # [1, BDI]
            b_t, c_t = b_g[i:i + 1], c_g[i:i + 1]              # [1, DS]
            a_t = jnp.exp(d_t.T * a_mat) * keep_g[i:i + 1].T   # [BDI, DS]
            bx_t = (d_t * x_t).T * b_t                         # [BDI, DS]
            h = a_t * h + bx_t
            ys.append(jnp.sum(h * c_t, axis=1, keepdims=True).T)  # [1, BDI]
        y_ref[rows, :] = jnp.concatenate(ys, axis=0).astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bt // grp, group, h_ref[...])
    h_ref[...] = h

    @pl.when(t_idx == n_t - 1)
    def _finish():
        hlast_ref[...] = h.astype(hlast_ref.dtype)


def mamba_scan_pallas(delta, xs, B, C, A, reset, h0, *,
                      block_t: int = DEFAULT_BT,
                      block_di: int = DEFAULT_BDI,
                      interpret: bool = False
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """See module docstring. T must divide block_t (caller pads); DI must
    divide block_di."""
    T, DI = delta.shape
    DS = B.shape[1]
    bt = min(block_t, T)
    bdi = min(block_di, DI)
    assert T % bt == 0 and DI % bdi == 0, (T, bt, DI, bdi)
    n_t, n_di = T // bt, DI // bdi

    kernel = functools.partial(_kernel, n_t=n_t, bt=bt)
    y, h_last = pl.pallas_call(
        kernel,
        grid=(n_di, n_t),
        in_specs=[
            pl.BlockSpec((bt, bdi), lambda d, t: (t, d)),   # delta
            pl.BlockSpec((bt, bdi), lambda d, t: (t, d)),   # xs
            pl.BlockSpec((bt, DS), lambda d, t: (t, 0)),    # B
            pl.BlockSpec((bt, DS), lambda d, t: (t, 0)),    # C
            pl.BlockSpec((bdi, DS), lambda d, t: (d, 0)),   # A
            pl.BlockSpec((bt, 1), lambda d, t: (t, 0)),     # reset
            pl.BlockSpec((bdi, DS), lambda d, t: (d, 0)),   # h0
        ],
        out_specs=[
            pl.BlockSpec((bt, bdi), lambda d, t: (t, d)),   # y
            pl.BlockSpec((bdi, DS), lambda d, t: (d, 0)),   # h_last
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, DI), delta.dtype),
            jax.ShapeDtypeStruct((DI, DS), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bdi, DS), jnp.float32)],
        interpret=interpret,
    )(delta, xs, B, C, A, reset.reshape(T, 1).astype(jnp.int32), h0)
    return y, h_last
