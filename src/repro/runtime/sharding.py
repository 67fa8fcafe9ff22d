"""Parameter/batch/optimizer sharding rules.

Layout (mesh axes: optional "pod" (DP), "data" (pipeline stages), "model"
(SP/FSDP/EP) — DESIGN.md §2.2):

* layer parameters are stage-stacked: leaf [L, ...] -> [d_p, L/d_p, ...],
  dim 0 sharded over "data";
* every leaf is additionally ZeRO-3 sharded over "model" along its largest
  divisible weight dim (the executor all-gathers per layer on use and the
  autodiff transpose emits the matching reduce-scatter);
* MoE expert weights are EP-sharded over "model" along the expert dim
  and are NOT gathered (expert parallelism instead of ZeRO for those);
* embedding / LM head are vocab-sharded over "model" (vocab-parallel
  embed-psum + streaming-CE merge live in runtime/sp.py);
* everything is replicated over "pod" (per-pod gradient psum once per step).
"""

from __future__ import annotations

import re
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

__all__ = ["EP_PATH_RE", "stack_stages", "stack_grouped_stages",
           "stage_active_mask", "interleaved_layer_order", "restack_elastic",
           "unstack_stages", "zero3_dim", "shard_dim_tree",
           "stage_param_specs", "head_param_specs", "batch_specs",
           "tree_paths_map", "mesh_axis_names",
           "gather_layer_params", "gather_stage_params", "gather_params"]

# expert-parallel leaves: sharded on their expert dim, never ZeRO-gathered
EP_PATH_RE = re.compile(r"moe/(w_gate|w_up|w_down)$")


def mesh_axis_names(mesh: Mesh) -> Tuple[Optional[str], str, str]:
    """Returns (pod_axis | None, data_axis, model_axis)."""
    names = mesh.axis_names
    if len(names) == 3:
        return names[0], names[1], names[2]
    if len(names) == 2:
        return None, names[0], names[1]
    raise ValueError(f"expected 2 or 3 mesh axes, got {names}")


def tree_paths_map(fn, tree):
    """tree_map with a '/'-joined key path passed first."""
    def _name(k) -> str:
        if hasattr(k, "key"):
            return str(k.key)
        if hasattr(k, "idx"):
            return str(k.idx)
        return str(k)

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: fn("/".join(_name(k) for k in path), leaf), tree)


def _stack_one(layers_tree, n_stages: int, L_ps: int, order=None):
    """[L, ...] leaves -> [n_stages, L_ps, ...], zero-padded layer slots.
    ``order`` optionally permutes the padded layer list before reshaping
    (interleaved virtual-stage placement)."""
    def _re(x):
        pad = n_stages * L_ps - x.shape[0]
        if pad:
            x = jnp.concatenate(
                [x, jnp.zeros((pad, *x.shape[1:]), x.dtype)], axis=0)
        if order is not None:
            x = x[order]
        return x.reshape(n_stages, L_ps, *x.shape[1:])
    return jax.tree.map(_re, layers_tree)


def interleaved_layer_order(d_p: int, layers_per_stage: int, v: int):
    """Padded-layer permutation for ``interleaved-1f1b`` stacking.

    Global virtual stage ``s = j * d_p + p`` (device ``p`` hosting local
    virtual stage ``j``) owns the contiguous padded layers
    ``[s * L_v, (s + 1) * L_v)`` where ``L_v = layers_per_stage / v`` — the
    Megatron-style round-robin placement that shortens the pipeline fill by
    ``v``. Returns ``order`` with
    ``stacked[p, j * L_v + l] = layers[order[p * L_ps + j * L_v + l]]``.
    Identity at ``v == 1``.
    """
    import numpy as np
    if layers_per_stage % v:
        raise ValueError(
            f"v={v} must divide layers_per_stage={layers_per_stage}")
    L_v = layers_per_stage // v
    p_idx, j, l = np.meshgrid(np.arange(d_p), np.arange(v), np.arange(L_v),
                              indexing="ij")
    order = ((j * d_p + p_idx) * L_v + l).reshape(-1)
    return order


def stack_stages(layers_tree, d_p: int, n_layers: int, v: int = 1):
    """[L, ...] leaves -> [d_p, ceil(L/d_p), ...], zero-padded.

    Non-divisible depths (gemma3: 26 over 16 stages) pad with inert layer
    slots; :func:`stage_active_mask` marks them and the executor turns the
    padded layers into identity (the compute waste is real and surfaces in
    the roofline's MODEL_FLOPS ratio — DESIGN.md §2.1).

    ``v > 1`` stacks for ``interleaved-1f1b``: device ``p``'s ``v`` local
    virtual-stage blocks hold the layers of global virtual stages
    ``j * d_p + p`` (:func:`interleaved_layer_order`), so the layer order a
    chunk traverses around the ring is the model's own.
    """
    L_ps = -(-n_layers // d_p)
    order = interleaved_layer_order(d_p, L_ps, v) if v > 1 else None
    return _stack_one(layers_tree, d_p, L_ps, order)


def stack_grouped_stages(groups, L_ps: int):
    """Stack several homogeneous layer groups into one stage-stacked tree.

    ``groups`` is a list of ``(layers_tree, n_stages)``: each tree's
    ``[L, ...]`` leaves pad to ``n_stages * L_ps`` inert slots and reshape
    to ``[n_stages, L_ps, ...]``; the groups then concatenate along the
    stage dim (used by the enc-dec pipeline, whose encoder stages precede
    the decoder stages in one uniform pytree)."""
    stacked = [_stack_one(tree, n_stages, L_ps) for tree, n_stages in groups]
    if len(stacked) == 1:
        return stacked[0]
    out = stacked[0]
    for nxt in stacked[1:]:
        out = jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=0),
                           out, nxt)
    return out


def stage_active_mask(d_p: int, n_layers: int, v: int = 1):
    """[d_p, ceil(L/d_p)] bool: True where a real layer lives (under the
    ``v``-way interleaved placement when ``v > 1``)."""
    import numpy as np
    L_ps = -(-n_layers // d_p)
    flat = np.arange(d_p * L_ps) < n_layers
    if v > 1:
        flat = flat[interleaved_layer_order(d_p, L_ps, v)]
    return jnp.asarray(flat.reshape(d_p, L_ps))


def restack_elastic(saved, new_dp: int, new_ls: int, n_layers: int,
                    v: int = 1):
    """Adapt one stage-stacked ``[d_p_old, L_s_old, ...]`` array to a new
    pipeline depth (elastic checkpoint reshard): un-permute the saved
    layout back to model layer order (interleaved placement included),
    strip the old padding, re-pad and re-stack for ``(new_dp, new_ls)``
    under the same ``v``. Host-side numpy; returns ``None`` when the
    layout cannot be adapted (fewer slots than layers, or ``v`` not
    dividing a block size) — the caller falls back to fresh init.
    """
    import numpy as np
    d_p_old, L_s_old = saved.shape[0], saved.shape[1]
    if (new_dp * new_ls < n_layers or L_s_old % max(v, 1)
            or new_ls % max(v, 1)):
        return None
    flat = np.asarray(saved).reshape(d_p_old * L_s_old, *saved.shape[2:])
    if v > 1:
        order = interleaved_layer_order(d_p_old, L_s_old, v)
        inv = np.empty_like(order)
        inv[order] = np.arange(order.size)
        flat = flat[inv]
    flat = flat[:n_layers]
    pad = new_dp * new_ls - n_layers
    if pad:
        flat = np.concatenate(
            [flat, np.zeros((pad, *flat.shape[1:]), flat.dtype)])
    if v > 1:
        flat = flat[interleaved_layer_order(new_dp, new_ls, v)]
    return flat.reshape(new_dp, new_ls, *flat.shape[1:])


def unstack_stages(layers_tree, n_layers: int, v: int = 1):
    def _re(x):
        flat = x.reshape(x.shape[0] * x.shape[1], *x.shape[2:])
        if v > 1:
            import numpy as np
            order = interleaved_layer_order(x.shape[0], x.shape[1], v)
            inv = np.empty_like(order)
            inv[order] = np.arange(order.size)
            flat = flat[inv]
        return flat[:n_layers]
    return jax.tree.map(_re, layers_tree)


def _lookup_path(tree, path: str):
    node = tree
    for key in path.split("/"):
        node = node[key]
    return node


def gather_params(tree, shard_dims, axis: str, *, dim_offset: int):
    """ZeRO-3: materialize full parameters from "model" shards.

    ``shard_dims`` is the precomputed tree of gather dims in FULL-shape
    coordinates (including the [d_p, L_s] stacking prefix); ``dim_offset``
    subtracts the prefix dims already stripped from ``tree``'s leaves
    (2 for a single layer's tree, 1 for a whole stage's [L_s, ...] tree).
    EP leaves carry a marker dim but stay sharded (expert parallelism),
    which :data:`EP_PATH_RE` expresses by pointing at the expert dim; the
    path check below skips them.
    """
    def _g(path, leaf):
        if EP_PATH_RE.search(path):
            return leaf
        zd = _lookup_path(shard_dims, path)
        if zd is None:
            return leaf
        return jax.lax.all_gather(leaf, axis, axis=zd - dim_offset,
                                  tiled=True)
    return tree_paths_map(_g, tree)


def gather_layer_params(lp, shard_dims, axis: str):
    """ZeRO-3 'per_tick' mode: gather one layer's full parameters."""
    return gather_params(lp, shard_dims, axis, dim_offset=2)


def gather_stage_params(stage_params, shard_dims, axis: str):
    """ZeRO-3 'per_step' mode: gather the whole stage's stacked [L_s, ...]
    tree once; leaves keep their L_s dim so the gather axis is zd - 1."""
    return gather_params(stage_params, shard_dims, axis, dim_offset=1)


def zero3_dim(path: str, shape: Tuple[int, ...], d_s: int,
              first_dim: int = 2) -> Optional[int]:
    """Pick the ZeRO-3 shard dim for a stage-stacked leaf [d_p, L_s, ...]:
    the FIRST trailing dim divisible by d_s (None => replicated). Must be
    called with FULL (unsharded) shapes — the executor receives the chosen
    dims precomputed (shard_dim_tree) so local views can't disagree."""
    if EP_PATH_RE.search(path):
        return first_dim  # expert dim ([d_p, L_s, E, ...])
    for d in range(first_dim, len(shape)):
        if shape[d] % d_s == 0:
            return d
    return None


def shard_dim_tree(stacked_tree, d_s: int):
    """Pytree (same structure) of Optional[int] ZeRO gather dims, computed
    from full stacked shapes."""
    return tree_paths_map(
        lambda path, leaf: zero3_dim(path, leaf.shape, d_s), stacked_tree)


def stage_param_specs(stacked_tree, d_s: int, *, pod: Optional[str],
                      data: str = "data", model: str = "model"):
    """PartitionSpec tree for stage-stacked layer params."""
    def _spec(path: str, leaf) -> P:
        dims: List[Optional[str]] = [None] * leaf.ndim
        dims[0] = data
        zd = zero3_dim(path, leaf.shape, d_s)
        if zd is not None:
            dims[zd] = model
        return P(*dims)
    return tree_paths_map(_spec, stacked_tree)


def head_param_specs(head_tree, d_s: int, *, model: str = "model"):
    """Embed / unembed / final_norm: vocab (dim 0) or feature sharding."""
    def _spec(path: str, leaf) -> P:
        if leaf.ndim >= 2:          # [V, D] embed/unembed
            return P(model, *([None] * (leaf.ndim - 1)))
        if leaf.shape and leaf.shape[0] % d_s == 0:
            return P(model)
        return P()
    return tree_paths_map(_spec, head_tree)


def batch_specs(batch_tree, *, pod: Optional[str], model: str = "model",
                replicated: Tuple[str, ...] = ()):
    """Chunked batch arrays [(pods,) n_chunks, cap, ...]: chunk dim over pod
    (if present), token dim over model. Leaves whose key path matches a name
    in ``replicated`` stay fully replicated over the model axis — the
    serving engine's per-token page table is one: every rank gathers cache
    pages it owns for ALL tokens of the step, so it needs the whole table."""
    def _spec(path: str, leaf) -> P:
        dims: List[Optional[str]] = [None] * leaf.ndim
        i = 0
        if pod is not None:
            dims[0] = pod
            i = 1
        if path in replicated:
            return P(*dims)
        if leaf.ndim > i + 1:
            dims[i + 1] = model   # token/capacity dim
        return P(*dims)
    return tree_paths_map(_spec, batch_tree)
