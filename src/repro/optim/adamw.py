"""AdamW with ZeRO-3-partitioned state.

Runs on LOCAL shards inside ``shard_map``: because gradients come out of
autodiff with exactly the parameters' sharding (the ZeRO all-gather
transposes to a reduce-scatter), the optimizer never communicates — each
device updates its own param/master/m/v shard. fp32 master weights + m/v;
live params in the executor's dtype (bf16 by default).

Gradient clipping needs one global norm: the caller supplies ``psum_axes``
so the sum of squares can cross the ("data", "model") shards (and "pod"
after the pod gradient reduction).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

__all__ = ["AdamWConfig", "init_opt_state", "adamw_update", "global_norm"]


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10000
    min_lr_frac: float = 0.1


def init_opt_state(params) -> Dict[str, Any]:
    # copy=True: with float32 params astype() would RETURN THE SAME buffer,
    # and a step that donates both params and opt.master then aborts with
    # "attempt to donate the same buffer twice" (surfaced by the AOT-
    # compiled step path, which does not re-layout already-placed inputs)
    master = jax.tree.map(lambda p: jnp.array(p, jnp.float32, copy=True),
                          params)
    zeros = lambda: jax.tree.map(  # noqa: E731
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return {"master": master, "m": zeros(), "v": zeros(),
            "step": jnp.zeros((), jnp.int32)}


def lr_schedule(cfg: AdamWConfig, step: jnp.ndarray) -> jnp.ndarray:
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    prog = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
                    0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    frac = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * cos
    return cfg.lr * warm * frac


def global_norm(grads, psum_axes: Sequence[str]) -> jnp.ndarray:
    sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
             for g in jax.tree.leaves(grads))
    for ax in psum_axes:
        sq = jax.lax.psum(sq, ax)
    return jnp.sqrt(sq)


def adamw_update(cfg: AdamWConfig, params, grads, state, *,
                 grad_scale: jnp.ndarray,
                 psum_axes: Sequence[str] = (),
                 gnorm: Optional[jnp.ndarray] = None
                 ) -> Tuple[Any, Dict, Dict]:
    """One AdamW step on local shards. ``grad_scale`` rescales summed-loss
    gradients to per-token means (1 / n_valid_tokens). Callers inside
    shard_map pass a precomputed ``gnorm`` (replication-factor aware)."""
    with jax.named_scope("optimizer"):
        step = state["step"] + 1
        grads = jax.tree.map(
            lambda g: g.astype(jnp.float32) * grad_scale, grads)
        if gnorm is None:
            gnorm = global_norm(grads, psum_axes)
        else:
            gnorm = gnorm * grad_scale
        clip = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-12))
        lr = lr_schedule(cfg, step)
        b1, b2 = cfg.beta1, cfg.beta2
        c1 = 1.0 - b1 ** step.astype(jnp.float32)
        c2 = 1.0 - b2 ** step.astype(jnp.float32)

        def upd(p_master, g, m, v):
            g = g * clip
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * jnp.square(g)
            mh = m / c1
            vh = v / c2
            new_master = p_master - lr * (
                mh / (jnp.sqrt(vh) + cfg.eps) + cfg.weight_decay * p_master)
            return new_master, m, v

        flat_p, treedef = jax.tree.flatten(state["master"])
        flat_g = jax.tree.leaves(grads)
        flat_m = jax.tree.leaves(state["m"])
        flat_v = jax.tree.leaves(state["v"])
        out = [upd(p, g, m, v)
               for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
        new_master = jax.tree.unflatten(treedef, [o[0] for o in out])
        new_m = jax.tree.unflatten(treedef, [o[1] for o in out])
        new_v = jax.tree.unflatten(treedef, [o[2] for o in out])
        new_params = jax.tree.map(
            lambda live, mast: mast.astype(live.dtype), params, new_master)
        new_state = {"master": new_master, "m": new_m, "v": new_v,
                     "step": step}
        metrics = {"grad_norm": gnorm, "lr": lr}
        return new_params, new_state, metrics
