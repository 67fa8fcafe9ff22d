"""Decoder language model composing every assigned family.

Layout decisions are driven by the pipeline executor:

* layer parameters are **stacked** along a leading ``L`` axis (one pytree
  whose leaves have shape ``[L, ...]``) so a pipeline stage can hold the
  ``[L/d_p, ...]`` shard and scan over its layers;
* every layer exposes a *context carry* — attention KV (or MLA latent)
  buffers plus SSM ``(h, conv_tail)`` — so split chunks thread their causal
  context through the 1F1B schedule; the carry's autodiff cotangent is
  exactly the paper's dKV term (Eq. 5);
* embedding and the (fused, vocab-tiled) CE head live OUTSIDE the layer
  stack: the executor runs them before/after the pipeline region.

The reference path (`forward_chunk` / `chunk_loss`) is single-device,
exact, and differentiable — the oracle for executor-equivalence tests.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ref import streaming_cross_entropy

from .attention import (attention_block, init_attention,
                        make_local_attention_policy)
from .config import ArchConfig, LayerKind
from .layers import embed_init, rms_norm, swiglu_apply, swiglu_init
from .moe import init_moe, moe_apply_dense
from .ssm import init_mamba, mamba_apply, ssm_state_shape

__all__ = ["DecoderLM", "LayerCtx", "kv_buffer_shape"]


class LayerCtx(NamedTuple):
    """Per-layer split-chunk context carry (None fields where inapplicable)."""
    k: Optional[jnp.ndarray]          # [C_cap, Hkv, Dh] or MLA rows [C_cap,1,r+rr]
    v: Optional[jnp.ndarray]
    ssm_h: Optional[jnp.ndarray]      # [di, ds] fp32
    ssm_tail: Optional[jnp.ndarray]   # [K-1, di]


def kv_buffer_shape(cfg: ArchConfig, cap: int) -> Optional[Tuple[Tuple[int, ...], Tuple[int, ...]]]:
    s = cfg.spec
    if s.attn_free:
        return None
    if s.kv_lora_rank > 0:
        return ((cap, 1, s.kv_lora_rank + s.qk_rope_dim), (cap, 1, 0))
    return ((cap, s.n_kv_heads, s.head_dim), (cap, s.n_kv_heads, s.head_dim))


class DecoderLM:
    """init/apply-style decoder LM parameterized by ArchConfig."""

    def __init__(self, cfg: ArchConfig, *,
                 attn_fn: Optional[Callable] = None,
                 moe_fn: Optional[Callable] = None,
                 ssm_scan_fn: Optional[Callable] = None,
                 ssm_tail_exchange: Optional[Callable] = None):
        """``attn_fn`` is an attention *policy* (see models/attention.py);
        ``moe_fn``/``ssm_scan_fn``/``ssm_tail_exchange`` are the MoE and SSM
        injection points the distributed runtime replaces."""
        self.cfg = cfg
        self.attn_fn = attn_fn or make_local_attention_policy()
        self.moe_fn = moe_fn or moe_apply_dense
        self.ssm_scan_fn = ssm_scan_fn
        self.ssm_tail_exchange = ssm_tail_exchange

    # ------------------------------------------------------------------
    # Init.
    # ------------------------------------------------------------------
    def _init_layer(self, key, dtype) -> Dict:
        cfg, s = self.cfg, self.cfg.spec
        ks = jax.random.split(key, 4)
        p: Dict[str, Any] = {"ln1": jnp.zeros((s.d_model,), dtype)}
        kind = cfg.layer_kind
        if kind in (LayerKind.ATTN, LayerKind.MOE, LayerKind.HYBRID):
            p["attn"] = init_attention(cfg, ks[0], dtype)
        if kind in (LayerKind.MAMBA, LayerKind.HYBRID):
            p["mamba"] = init_mamba(cfg, ks[1], dtype)
        if kind != LayerKind.MAMBA:
            p["ln2"] = jnp.zeros((s.d_model,), dtype)
            if s.n_experts > 0:
                p["moe"] = init_moe(cfg, ks[2], dtype)
            else:
                p["mlp"] = swiglu_init(ks[2], s.d_model, s.d_ff, dtype)
        return p

    def init(self, key, dtype=jnp.float32) -> Dict:
        cfg, s = self.cfg, self.cfg.spec
        k_embed, k_layers, k_head = jax.random.split(key, 3)
        layer_keys = jax.random.split(k_layers, s.n_layers)
        layers = jax.vmap(lambda k: self._init_layer(k, dtype))(layer_keys)
        params = {
            "embed": embed_init(k_embed, s.vocab, s.d_model, dtype),
            "layers": layers,
            "final_norm": jnp.zeros((s.d_model,), dtype),
        }
        if not cfg.tie_embeddings:
            params["unembed"] = embed_init(k_head, s.vocab, s.d_model, dtype)
        return params

    def head_weights(self, params: Dict) -> jnp.ndarray:
        return params.get("unembed", params["embed"])

    # ------------------------------------------------------------------
    # Embedding / head.
    # ------------------------------------------------------------------
    def embed(self, params: Dict, tokens: jnp.ndarray,
              compute_dtype=jnp.bfloat16) -> jnp.ndarray:
        cfg, s = self.cfg, self.cfg.spec
        with jax.named_scope("embed"):
            x = params["embed"][tokens].astype(compute_dtype)
            if cfg.embed_scale:
                x = x * jnp.asarray(s.d_model ** 0.5, compute_dtype)
        return x

    def chunk_loss(self, params: Dict, hidden: jnp.ndarray,
                   targets: jnp.ndarray, seg: jnp.ndarray
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(sum_loss, n_valid) via the streaming fused CE."""
        cfg = self.cfg
        with jax.named_scope("ce_head"):
            h = rms_norm(hidden, params["final_norm"], cfg.rms_eps)
            valid = (seg >= 0) & (targets >= 0)
            return streaming_cross_entropy(h, self.head_weights(params),
                                           jnp.maximum(targets, 0), valid)

    # ------------------------------------------------------------------
    # One layer (the unit the pipeline scans).
    # ------------------------------------------------------------------
    def layer_apply(self, lparams: Dict, x: jnp.ndarray, *,
                    pos: jnp.ndarray, seg: jnp.ndarray,
                    ctx: LayerCtx, ctx_len: jnp.ndarray,
                    window: jnp.ndarray | int,
                    positions3: Optional[jnp.ndarray] = None,
                    memory: Optional[Tuple] = None
                    ) -> Tuple[jnp.ndarray, LayerCtx]:
        """x: [T, D] -> (x', updated context carry).

        The carry update *appends* the current chunk's KV rows at offset
        ``ctx_len`` (dynamic_update_slice) and advances the SSM state; the
        executor decides when to reset (tail chunk completed).
        """
        cfg, s = self.cfg, self.cfg.spec
        kind = cfg.layer_kind
        h = rms_norm(x, lparams["ln1"], cfg.rms_eps)
        mixer_out = jnp.zeros_like(x)
        new_k, new_v = ctx.k, ctx.v
        new_h, new_tail = ctx.ssm_h, ctx.ssm_tail

        if kind in (LayerKind.ATTN, LayerKind.MOE, LayerKind.HYBRID):
            attn_out, nk, nv = attention_block(
                cfg, lparams["attn"], h, pos=pos, seg=seg,
                ctx_k=ctx.k, ctx_v=ctx.v, ctx_len=ctx_len,
                window=window, attn_fn=self.attn_fn, positions3=positions3)
            mixer_out = mixer_out + attn_out
            if ctx.k is not None:
                new_k = nk
                new_v = nv if nv is not None else ctx.v
        if kind in (LayerKind.MAMBA, LayerKind.HYBRID):
            m_out, new_h, new_tail = mamba_apply(
                cfg, lparams["mamba"], h, pos=pos,
                state=ctx.ssm_h, conv_tail=ctx.ssm_tail,
                scan_fn=self.ssm_scan_fn,
                tail_exchange=self.ssm_tail_exchange)
            if kind == LayerKind.HYBRID:
                mixer_out = 0.5 * (mixer_out + m_out)
            else:
                mixer_out = m_out
        x = x + mixer_out

        if kind != LayerKind.MAMBA:
            h2 = rms_norm(x, lparams["ln2"], cfg.rms_eps)
            if s.n_experts > 0:
                x = x + self.moe_fn(cfg, lparams["moe"], h2)
            else:
                with jax.named_scope("mlp"):
                    x = x + swiglu_apply(lparams["mlp"], h2)
        return x, LayerCtx(new_k, new_v, new_h, new_tail)

    # ------------------------------------------------------------------
    # Whole-model reference forward over one packed chunk.
    # ------------------------------------------------------------------
    def init_ctx(self, cap: int, compute_dtype=jnp.bfloat16,
                 n_layers: Optional[int] = None) -> LayerCtx:
        """Stacked context carry for ``n_layers`` (default: all layers)."""
        cfg, s = self.cfg, self.cfg.spec
        L = n_layers if n_layers is not None else s.n_layers
        kv = kv_buffer_shape(cfg, cap)
        k = v = hh = tail = None
        if kv is not None:
            k = jnp.zeros((L, *kv[0]), compute_dtype)
            v = jnp.zeros((L, *kv[1]), compute_dtype)
        if s.ssm_state > 0:
            (hs, ts) = ssm_state_shape(cfg)
            hh = jnp.zeros((L, *hs), jnp.float32)
            tail = jnp.zeros((L, *ts), compute_dtype)
        return LayerCtx(k, v, hh, tail)

    def forward_chunk(self, params: Dict, tokens: jnp.ndarray,
                      seg: jnp.ndarray, pos: jnp.ndarray, *,
                      ctx: Optional[LayerCtx] = None,
                      ctx_len: jnp.ndarray | int = 0,
                      positions3: Optional[jnp.ndarray] = None,
                      compute_dtype=jnp.bfloat16
                      ) -> Tuple[jnp.ndarray, Optional[LayerCtx]]:
        """Run all layers over one packed chunk. Returns (hidden, new ctx)."""
        cfg, s = self.cfg, self.cfg.spec
        x = self.embed(params, tokens, compute_dtype)
        windows = jnp.asarray(cfg.layer_windows(), jnp.int32)
        ctx_len = jnp.asarray(ctx_len, jnp.int32)
        if ctx is None:
            # context-free execution: None fields skip both the ctx concat in
            # attention and the buffer append (pytree-transparent).
            ctx = LayerCtx(None, None, None, None)

        def body(x, per_layer):
            lp, w, lctx = per_layer
            x, new_ctx = self.layer_apply(
                lp, x, pos=pos, seg=seg, ctx=lctx, ctx_len=ctx_len,
                window=w, positions3=positions3)
            return x, new_ctx

        x, new_ctx = jax.lax.scan(body, x, (params["layers"], windows, ctx))
        return x, new_ctx

    def loss(self, params: Dict, tokens, targets, seg, pos, *,
             positions3=None, compute_dtype=jnp.bfloat16
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        hidden, _ = self.forward_chunk(params, tokens, seg, pos,
                                       positions3=positions3,
                                       compute_dtype=compute_dtype)
        return self.chunk_loss(params, hidden, targets, seg)


def _append_rows(buf: jnp.ndarray, rows: jnp.ndarray,
                 offset: jnp.ndarray) -> jnp.ndarray:
    """Write ``rows`` into ``buf`` starting at ``offset`` (clamped)."""
    return jax.lax.dynamic_update_slice_in_dim(
        buf, rows.astype(buf.dtype), offset, axis=0)
