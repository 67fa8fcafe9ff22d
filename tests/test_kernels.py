"""Per-kernel allclose tests: Pallas (interpret=True) vs pure-jnp oracles,
sweeping shapes and dtypes per the assignment."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ref
from repro.kernels.cross_entropy import (cross_entropy_bwd_dh_pallas,
                                         cross_entropy_bwd_dw_pallas,
                                         cross_entropy_fwd_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.mamba_scan import mamba_scan_pallas
from repro.kernels.ops import fused_cross_entropy, mamba_scan

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _varlen_meta(key, T, n_seq, ctx=0):
    """Random packed layout: n_seq segments + optional context prefix rows."""
    cuts = np.sort(np.asarray(
        jax.random.choice(key, np.arange(1, T), (n_seq - 1,), replace=False)))
    bounds = np.concatenate([[0], cuts, [T]])
    seg = np.full((T,), -1, np.int32)
    pos = np.zeros((T,), np.int32)
    for s in range(n_seq):
        a, b = bounds[s], bounds[s + 1]
        seg[a:b] = s
        pos[a:b] = np.arange(b - a) + (ctx if s == 0 else 0)
    return jnp.asarray(seg), jnp.asarray(pos)


# ---------------------------------------------------------------------------
# Flash attention.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("T,Hq,Hkv,Dh,nseq", [
    (64, 4, 4, 32, 1),      # MHA single sequence
    (128, 8, 2, 64, 3),     # GQA packed
    (96, 4, 1, 128, 2),     # MQA (gemma3-style kv=1)
    (256, 2, 2, 16, 4),     # many segments, small heads
])
def test_flash_attention_matches_oracle(dtype, T, Hq, Hkv, Dh, nseq):
    key = jax.random.PRNGKey(T + Hq)
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (T, Hq, Dh), dtype)
    k = jax.random.normal(ks[1], (T, Hkv, Dh), dtype)
    v = jax.random.normal(ks[2], (T, Hkv, Dh), dtype)
    seg, pos = _varlen_meta(ks[3], T, nseq)
    out_p = flash_attention_pallas(q, k, v, seg, seg, pos, pos,
                                   block_q=32, block_kv=32, interpret=True)
    out_r = ref.flash_attention_reference(q, k, v, seg, seg, pos, pos)
    np.testing.assert_allclose(np.asarray(out_p, np.float32),
                               np.asarray(out_r, np.float32), **TOL[dtype])


def test_flash_attention_context_kv():
    """Split-chunk case: KV includes a context prefix of an earlier slice."""
    key = jax.random.PRNGKey(7)
    T, C, H, Dh = 64, 32, 4, 32
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (T, H, Dh))
    k = jax.random.normal(ks[1], (C + T, H, Dh))
    v = jax.random.normal(ks[2], (C + T, H, Dh))
    seg_q, pos_q = _varlen_meta(key, T, 2, ctx=C)
    seg_kv = jnp.concatenate([jnp.zeros(C, jnp.int32), seg_q])
    pos_kv = jnp.concatenate([jnp.arange(C), pos_q])
    out_p = flash_attention_pallas(q, k, v, seg_q, seg_kv, pos_q, pos_kv,
                                   block_q=32, block_kv=32, interpret=True)
    out_r = ref.flash_attention_reference(q, k, v, seg_q, seg_kv,
                                          pos_q, pos_kv)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [1, 8, 31])
def test_flash_attention_sliding_window(window):
    key = jax.random.PRNGKey(11)
    T, H, Dh = 128, 2, 32
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (T, H, Dh))
    k = jax.random.normal(ks[1], (T, H, Dh))
    v = jax.random.normal(ks[2], (T, H, Dh))
    seg, pos = _varlen_meta(ks[2], T, 2)
    out_p = flash_attention_pallas(q, k, v, seg, seg, pos, pos,
                                   window=window, block_q=32, block_kv=32,
                                   interpret=True)
    out_r = ref.flash_attention_reference(q, k, v, seg, seg, pos, pos,
                                          window=window)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=2e-5, atol=2e-5)


def test_blocked_ref_matches_naive_ref():
    """The dry-run's blocked-jnp path is pinned to the same oracle."""
    key = jax.random.PRNGKey(3)
    T, H, Dh = 160, 4, 32
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (T, H, Dh))
    k = jax.random.normal(ks[1], (T, H, Dh))
    v = jax.random.normal(ks[2], (T, H, Dh))
    seg, pos = _varlen_meta(ks[1], T, 3)
    a = ref.blocked_flash_attention(q, k, v, seg, seg, pos, pos, block_kv=64)
    b = ref.flash_attention_reference(q, k, v, seg, seg, pos, pos)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)


def _autodiff_blocked_attention(q, k, v, seg_q, seg_kv, pos_q, pos_kv, *,
                                causal=True, window=0, block_kv=512):
    """The online-softmax scan with no custom_vjp: autodiff differentiates
    through every tile (O(T * S) residuals). Reference for the FA-2
    backward of ``ref.blocked_flash_attention``."""
    T, Hq, Dh = q.shape
    scale = Dh ** -0.5
    k, v = ref._expand_kv(k, Hq), ref._expand_kv(v, Hq)
    pad = (-k.shape[0]) % block_kv
    k = jnp.concatenate([k, jnp.zeros((pad, *k.shape[1:]), k.dtype)])
    v = jnp.concatenate([v, jnp.zeros((pad, *v.shape[1:]), v.dtype)])
    seg_kv = jnp.concatenate([seg_kv, jnp.full((pad,), -2, seg_kv.dtype)])
    pos_kv = jnp.concatenate([pos_kv, jnp.zeros((pad,), pos_kv.dtype)])
    nb = k.shape[0] // block_kv

    def body(carry, blk):
        acc, m_run, l_run = carry
        kk, vv, sseg, ppos = blk
        s = jnp.einsum("thd,shd->hts", q, kk,
                       preferred_element_type=jnp.float32) * scale
        msk = ref._mask(seg_q, sseg, pos_q, ppos, causal, jnp.asarray(window))
        s = jnp.where(msk[None], s, ref.NEG_INF)
        m_new = jnp.maximum(m_run, s.max(axis=-1))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_run * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "hts,shd->htd", p, vv.astype(jnp.float32))
        return (acc, m_new, l_new), None

    init = (jnp.zeros((Hq, T, v.shape[-1]), jnp.float32),
            jnp.full((Hq, T), ref.NEG_INF, jnp.float32),
            jnp.zeros((Hq, T), jnp.float32))
    (acc, _, l_run), _ = jax.lax.scan(
        body, init, (k.reshape(nb, block_kv, Hq, Dh),
                     v.reshape(nb, block_kv, Hq, -1),
                     seg_kv.reshape(nb, block_kv),
                     pos_kv.reshape(nb, block_kv)))
    out = acc / jnp.maximum(l_run[..., None], 1e-30)
    return jnp.transpose(out, (1, 0, 2)).astype(q.dtype)


@pytest.mark.parametrize("causal,window,Hq,Hkv,S", [
    (True, 0, 4, 4, 96),      # causal MHA
    (True, 12, 4, 4, 96),     # sliding window
    (True, 0, 8, 2, 96),      # GQA
    (False, 0, 4, 2, 80),     # bidirectional GQA, padded KV tail (80 % 32)
    (True, 0, 4, 1, 112),     # MQA over a context prefix, padded KV tail
])
def test_blocked_attention_custom_vjp_matches_autodiff(causal, window, Hq,
                                                       Hkv, S):
    """The FA-2 backward (saves q, k, v, out, lse; re-scans KV tiles) gives
    the grads autodiff takes through the plain online-softmax scan."""
    T, Dh, C = 64, 16, S - 64
    ks = jax.random.split(jax.random.PRNGKey(S + Hq + window), 5)
    q = jax.random.normal(ks[0], (T, Hq, Dh))
    k = jax.random.normal(ks[1], (S, Hkv, Dh))
    v = jax.random.normal(ks[2], (S, Hkv, Dh))
    seg_q, pos_q = _varlen_meta(ks[3], T, 3, ctx=C)
    seg_kv = jnp.concatenate([jnp.zeros(C, jnp.int32), seg_q])
    pos_kv = jnp.concatenate([jnp.arange(C, dtype=jnp.int32), pos_q])
    ct = jax.random.normal(ks[4], (T, Hq, Dh))
    kw = dict(causal=causal, window=window, block_kv=32)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v, seg_q, seg_kv, pos_q, pos_kv,
                                   **kw) * ct).sum()

    lr, gr = jax.value_and_grad(loss(_autodiff_blocked_attention),
                                argnums=(0, 1, 2))(q, k, v)
    lc, gc = jax.value_and_grad(loss(ref.blocked_flash_attention),
                                argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(lc), float(lr), **TOL[jnp.float32])
    for a, b in zip(gc, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   **TOL[jnp.float32])


def test_blocked_attention_padding_rows_are_zero():
    """A query that attends to no key (padding, seg -1) outputs zeros and
    passes no gradient, as in the naive oracle."""
    T, H, Dh = 48, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q, k, v = (jax.random.normal(kk, (T, H, Dh)) for kk in ks)
    seg = jnp.where(jnp.arange(T) < 40, 0, -1).astype(jnp.int32)
    pos = jnp.arange(T, dtype=jnp.int32)
    out = ref.blocked_flash_attention(q, k, v, seg, seg, pos, pos,
                                      block_kv=16)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.flash_attention_reference(
            q, k, v, seg, seg, pos, pos)), **TOL[jnp.float32])
    assert not np.asarray(out[40:]).any()
    gq = jax.grad(lambda q: ref.blocked_flash_attention(
        q, k, v, seg, seg, pos, pos, block_kv=16).sum())(q)
    assert not np.asarray(gq[40:]).any()


# ---------------------------------------------------------------------------
# Fused cross entropy.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("T,D,V", [(32, 16, 100), (64, 32, 1000),
                                   (128, 64, 517)])
def test_ce_forward_matches_oracle(dtype, T, D, V):
    key = jax.random.PRNGKey(T + V)
    ks = jax.random.split(key, 3)
    h = jax.random.normal(ks[0], (T, D), dtype) * 0.5
    w = jax.random.normal(ks[1], (V, D), dtype) * 0.5
    tgt = jax.random.randint(ks[2], (T,), 0, V)
    valid = jnp.arange(T) % 5 != 0
    lse, tl = cross_entropy_fwd_pallas(h, w, tgt, valid, block_t=16,
                                       block_v=64, interpret=True)
    loss_p = ((lse - tl) * valid).sum()
    loss_r, n_r = ref.cross_entropy_reference(h, w, tgt, valid)
    tol = TOL[dtype].copy()
    np.testing.assert_allclose(float(loss_p), float(loss_r),
                               rtol=max(tol["rtol"], 1e-4))


def test_ce_backward_matches_autodiff():
    key = jax.random.PRNGKey(5)
    T, D, V = 48, 24, 301
    ks = jax.random.split(key, 3)
    h = jax.random.normal(ks[0], (T, D)) * 0.3
    w = jax.random.normal(ks[1], (V, D)) * 0.3
    tgt = jax.random.randint(ks[2], (T,), 0, V)
    valid = jnp.arange(T) % 3 != 1

    def loss_ref(h, w):
        s, n = ref.cross_entropy_reference(h, w, tgt, valid)
        return s
    dh_r, dw_r = jax.grad(loss_ref, argnums=(0, 1))(h, w)

    lse, _ = cross_entropy_fwd_pallas(h, w, tgt, valid, block_t=16,
                                      block_v=64, interpret=True)
    g_rows = valid.astype(jnp.float32)
    dh_p = cross_entropy_bwd_dh_pallas(h, w, tgt, lse, g_rows, block_t=16,
                                       block_v=64, interpret=True)
    dw_p = cross_entropy_bwd_dw_pallas(h, w, tgt, lse, g_rows, block_t=16,
                                       block_v=64, interpret=True)
    np.testing.assert_allclose(np.asarray(dh_p), np.asarray(dh_r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dw_p), np.asarray(dw_r),
                               rtol=1e-4, atol=1e-4)


def test_fused_ce_custom_vjp_end_to_end():
    """ops.fused_cross_entropy gradient (interpret mode) == naive autodiff."""
    key = jax.random.PRNGKey(9)
    T, D, V = 40, 16, 130
    ks = jax.random.split(key, 3)
    h = jax.random.normal(ks[0], (T, D)) * 0.3
    w = jax.random.normal(ks[1], (V, D)) * 0.3
    tgt = jax.random.randint(ks[2], (T,), 0, V)
    valid = jnp.ones((T,), bool)

    def mean_p(h, w):
        s, n = fused_cross_entropy(h, w, tgt, valid, block_t=16, block_v=64,
                                   interpret=True)
        return s / n

    def mean_r(h, w):
        s, n = ref.cross_entropy_reference(h, w, tgt, valid)
        return s / n

    lp, gp = jax.value_and_grad(mean_p, argnums=(0, 1))(h, w)
    lr, gr = jax.value_and_grad(mean_r, argnums=(0, 1))(h, w)
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gp[0]), np.asarray(gr[0]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gp[1]), np.asarray(gr[1]),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Mamba scan.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("T,DI,DS", [(32, 16, 4), (64, 32, 8), (96, 8, 16)])
def test_mamba_scan_matches_oracle(dtype, T, DI, DS):
    key = jax.random.PRNGKey(T + DI)
    ks = jax.random.split(key, 6)
    delta = jax.nn.softplus(jax.random.normal(ks[0], (T, DI))).astype(dtype)
    xs = jax.random.normal(ks[1], (T, DI), dtype)
    B = jax.random.normal(ks[2], (T, DS), dtype)
    C = jax.random.normal(ks[3], (T, DS), dtype)
    A = -jnp.exp(jax.random.normal(ks[4], (DI, DS)) * 0.3)
    reset = (jax.random.uniform(ks[5], (T,)) < 0.1).astype(jnp.int32)
    reset = reset.at[0].set(1)
    h0 = jax.random.normal(key, (DI, DS))

    y_p, h_p = mamba_scan_pallas(delta, xs, B, C, A.astype(dtype), reset,
                                 h0, block_t=16, block_di=8, interpret=True)
    # oracle
    a = jnp.exp(delta.astype(jnp.float32)[:, :, None] * A[None])
    a = jnp.where(reset.reshape(-1, 1, 1) > 0, 0.0, a)
    bx = (delta * xs).astype(jnp.float32)[:, :, None] * \
        B.astype(jnp.float32)[:, None, :]
    hs, h_r = ref.mamba_scan_reference(a, bx, h0.astype(jnp.float32))
    y_r = jnp.einsum("tds,ts->td", hs, C.astype(jnp.float32))
    # bf16 scan outputs accumulate like the carried state: same 3e-2 bound
    np.testing.assert_allclose(np.asarray(y_p, np.float32),
                               np.asarray(y_r, np.float32),
                               rtol=2e-5 if dtype == jnp.float32 else 3e-2,
                               atol=2e-5 if dtype == jnp.float32 else 3e-2)
    np.testing.assert_allclose(np.asarray(h_p), np.asarray(h_r),
                               rtol=1e-4 if dtype == jnp.float32 else 3e-2,
                               atol=1e-4 if dtype == jnp.float32 else 3e-2)


def test_mamba_scan_carry_state():
    """Scanning [0:T/2] then [T/2:T] with the carried state == one scan,
    including through the ops.py wrapper with padding."""
    key = jax.random.PRNGKey(21)
    T, DI, DS = 50, 8, 4   # deliberately not a multiple of the block
    ks = jax.random.split(key, 5)
    delta = jax.nn.softplus(jax.random.normal(ks[0], (T, DI)))
    xs = jax.random.normal(ks[1], (T, DI))
    B = jax.random.normal(ks[2], (T, DS))
    C = jax.random.normal(ks[3], (T, DS))
    A = -jnp.exp(jax.random.normal(ks[4], (DI, DS)) * 0.3)
    reset = jnp.zeros((T,), jnp.int32).at[0].set(1)
    h0 = jnp.zeros((DI, DS))

    y_full, h_full = mamba_scan(delta, xs, B, C, A, reset, h0,
                                block_t=16, interpret=True)
    half = T // 2
    y1, h_mid = mamba_scan(delta[:half], xs[:half], B[:half], C[:half], A,
                           reset[:half], h0, block_t=16, interpret=True)
    y2, h_end = mamba_scan(delta[half:], xs[half:], B[half:], C[half:], A,
                           jnp.zeros((T - half,), jnp.int32), h_mid,
                           block_t=16, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2])),
                               np.asarray(y_full), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_end), np.asarray(h_full),
                               rtol=1e-4, atol=1e-4)


@given(st.integers(2, 5), st.integers(1, 3), st.integers(0, 10 ** 6))
@settings(max_examples=20, deadline=None)
def test_flash_attention_property(n_heads_pow, nseq, seed):
    """Hypothesis sweep: random GQA ratios and segment layouts."""
    key = jax.random.PRNGKey(seed)
    Hq = 2 ** (n_heads_pow - 1)
    Hkv = max(1, Hq // 2)
    T, Dh = 64, 16
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (T, Hq, Dh))
    k = jax.random.normal(ks[1], (T, Hkv, Dh))
    v = jax.random.normal(ks[2], (T, Hkv, Dh))
    seg, pos = _varlen_meta(ks[3], T, nseq)
    out_p = flash_attention_pallas(q, k, v, seg, seg, pos, pos,
                                   block_q=16, block_kv=16, interpret=True)
    out_r = ref.flash_attention_reference(q, k, v, seg, seg, pos, pos)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=3e-5, atol=3e-5)
