"""Plain float32 reference of the Qwen3 decoder and its AdamW training step.

Written from the published Qwen3 description (Hugging Face
``modeling_qwen3``) and the configuration files beside it; it imports
nothing of the program under test. Per layer: RMSNorm, q/k/v projections,
per-head RMSNorm of q and k (qk-norm), rotary embedding over the whole
head (theta from the configuration, rotate-half pairing), grouped-query
attention (query head ``j`` reads key/value head ``j // (Hq / Hkv)``)
that is causal within each document of the packed batch, the output
projection, RMSNorm and a SwiGLU MLP, each added to the residual stream.
The head is the final RMSNorm and the (tied or untied) output matrix;
the loss is the mean next-token cross entropy over every token but each
document's last. Every matrix product runs at ``Precision.HIGHEST``.

Weights are made here from the run seed, in the canonical layout
(matrices ``[in, out]``, RMSNorm scales multiply), and the benchmark hands
the program the same numbers in its own layout.

Training keeps float32 master weights and computes each step with their
compute copies, the configuration's mixed precision: every matrix (the
embedding, the projections, the head) rounded to the configuration's
``compute_dtype`` and back, the RMSNorm scales as kept. The gradient at
the copies updates the masters (AdamW).

``precision="fp8"`` is the low-precision control: every projection, MLP
and head product takes float8 operands with one scale per tensor (e4m3
forward, e5m2 for the gradients), as an fp8 training step would.
``variant`` plants a fault in the reference for calibration: ``"half"``
leaves out the second half of the packed batch and takes the mean over the
rest; ``"unrefreshed"`` never refreshes the compute copies from the
masters, so every step computes with the first step's weights.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
NORM_LEAVES = ("ln1", "q_norm", "k_norm", "ln2", "final_norm")
# bytes a reference block may spend on one [heads, rows, keys] or
# [rows, vocab] f32 tensor
BLOCK_BYTES = 512 * 2 ** 20


def dims(cfg: Dict) -> Dict[str, int]:
    return {"L": int(cfg["num_hidden_layers"]), "D": int(cfg["hidden_size"]),
            "Hq": int(cfg["num_attention_heads"]),
            "Hkv": int(cfg["num_key_value_heads"]),
            "Dh": int(cfg["head_dim"]), "F": int(cfg["intermediate_size"]),
            "V": int(cfg["vocab_size"]),
            "tied": bool(cfg["tie_word_embeddings"])}


def run_key(seed: int):
    """A PRNG key from any seed >= 0 (more than 32 bits allowed)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def make_weights(cfg: Dict, key, dtype=jnp.bfloat16) -> Dict:
    """Canonical weights from ``key``: normal(0, initializer_range) for
    every matrix and the embedding, RMSNorm scales 1. Trace under jit."""
    d = dims(cfg)
    std = float(cfg["initializer_range"])
    L, D, Hq, Hkv, Dh, F, V = (d[k] for k in ("L", "D", "Hq", "Hkv", "Dh",
                                              "F", "V"))
    shapes = {"wq": (L, D, Hq * Dh), "wk": (L, D, Hkv * Dh),
              "wv": (L, D, Hkv * Dh), "wo": (L, Hq * Dh, D),
              "w_gate": (L, D, F), "w_up": (L, D, F), "w_down": (L, F, D)}
    keys = jax.random.split(key, len(shapes) + 2)
    layers = {n: (std * jax.random.normal(k, s, jnp.float32)).astype(dtype)
              for k, (n, s) in zip(keys, shapes.items())}
    for n, width in (("ln1", D), ("ln2", D), ("q_norm", Dh),
                     ("k_norm", Dh)):
        layers[n] = jnp.ones((L, width), dtype)
    w = {"embed": (std * jax.random.normal(keys[-2], (V, D), jnp.float32)
                   ).astype(dtype),
         "final_norm": jnp.ones((D,), dtype), "layers": layers}
    if not d["tied"]:
        w["unembed"] = (std * jax.random.normal(keys[-1], (V, D),
                                                jnp.float32)).astype(dtype)
    return w


def round_matrices(w: Dict, dtype) -> Dict:
    """Every matrix of ``w`` in ``dtype``, the RMSNorm scales as they are.
    The compute copy is this tree upcast to float32 in a program of its
    own: in one program the compiler may drop the pair of conversions and
    keep the weights unrounded (excess precision)."""
    def one(path, x):
        name = jax.tree_util.keystr(path)
        if any(f"'{n}'" in name for n in NORM_LEAVES):
            return x
        return x.astype(dtype)
    return jax.tree_util.tree_map_with_path(one, w)


def leaf_norms(w: Dict) -> Dict[str, jnp.ndarray]:
    """Frobenius norm of every leaf, one per layer for the layer stack."""
    out = {}
    for name, x in w.items():
        if name == "layers":
            for n, s in x.items():
                r = jnp.sqrt(jnp.sum(jnp.square(s.astype(jnp.float32)),
                                     axis=tuple(range(1, s.ndim))))
                for i in range(s.shape[0]):
                    out[f"layers.{i}.{n}"] = r[i]
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
    return out


def pack(docs: Sequence[np.ndarray], drop_half: bool = False
         ) -> Dict[str, np.ndarray]:
    """Documents back to back: tokens, next-token targets (-1 on each
    document's last token), and a document id per token."""
    tokens = np.concatenate(docs).astype(np.int32)
    targets = np.concatenate(
        [np.append(d[1:], -1) for d in docs]).astype(np.int32)
    seg = np.concatenate([np.full(len(d), i, np.int32)
                          for i, d in enumerate(docs)])
    pos = np.concatenate([np.arange(len(d), dtype=np.int32) for d in docs])
    if drop_half:
        targets[len(tokens) // 2:] = -1
    return {"tokens": tokens, "targets": targets, "seg": seg, "pos": pos}


# --- products -------------------------------------------------------------

def _q8(x, dtype):
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _dot8(a, b):
    return jnp.dot(_q8(a, jnp.float8_e4m3fn), _q8(b, jnp.float8_e4m3fn),
                   precision=HI)


def _dot8_fwd(a, b):
    return _dot8(a, b), (a, b)


def _dot8_bwd(res, g):
    a, b = res
    g8 = _q8(g, jnp.float8_e5m2)
    return (jnp.dot(g8, _q8(b, jnp.float8_e4m3fn).T, precision=HI),
            jnp.dot(_q8(a, jnp.float8_e4m3fn).T, g8, precision=HI))


_dot8.defvjp(_dot8_fwd, _dot8_bwd)


def _mm(x, w, precision: str):
    if precision == "fp8":
        return _dot8(x, w)
    if precision == "bf16":
        return jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    return jnp.dot(x, w, precision=HI)


def _block(n: int, row_bytes: int) -> int:
    """Largest power-of-two row block dividing ``n`` within BLOCK_BYTES."""
    b = 1
    while n % (2 * b) == 0 and 2 * b * row_bytes <= BLOCK_BYTES:
        b *= 2
    return b


def _rows(fn, x, *rest):
    """``fn`` over row blocks of ``x`` (and of the row-aligned ``rest``),
    recomputed in the backward pass: the reference never keeps one
    block's intermediates beyond its own use."""
    n = x.shape[0]
    b = _block(n, 4 * 16 * max(x.shape[-1], 1))
    xs = (x.reshape(n // b, b, *x.shape[1:]),) + tuple(
        r.reshape(n // b, b, *r.shape[1:]) for r in rest)
    out = jax.lax.map(lambda a: jax.checkpoint(fn)(*a), xs)
    return jax.tree.map(lambda o: o.reshape(n, *o.shape[2:]), out)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (np.arange(half, dtype=np.float64) / half)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, seg):
    """Softmax attention of every token over the earlier tokens of its own
    document, in blocks of query rows (q [N, Hq, Dh], k/v [N, Hkv, Dh])."""
    n, hq, dh = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    b = _block(n, 4 * hq * n)
    idx = jnp.arange(n)
    qb = q.reshape(n // b, b, hkv, g, dh)
    rows = idx.reshape(n // b, b)

    def one(qr, r):
        s = jnp.einsum("bhgd,nhd->hgbn", qr, k, precision=HI) / math.sqrt(dh)
        ok = (seg[r][:, None] == seg[None, :]) & (idx[None, :] <= r[:, None])
        p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        return jnp.einsum("hgbn,nhd->bhgd", p, v, precision=HI)

    out = jax.lax.map(lambda a: jax.checkpoint(one)(*a), (qb, rows))
    return out.reshape(n, hq, dh)


def _layer(cfg, lw, x, pos, seg, precision):
    d = dims(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    n = x.shape[0]
    h = _rms(x, lw["ln1"], eps)

    def qkv(hr):
        return (_mm(hr, lw["wq"], precision), _mm(hr, lw["wk"], precision),
                _mm(hr, lw["wv"], precision))

    q, k, v = _rows(qkv, h)
    q = _rms(q.reshape(n, d["Hq"], d["Dh"]), lw["q_norm"], eps)
    k = _rms(k.reshape(n, d["Hkv"], d["Dh"]), lw["k_norm"], eps)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    a = _attention(q, k, v.reshape(n, d["Hkv"], d["Dh"]), seg)
    x = x + _rows(lambda ar: _mm(ar, lw["wo"], precision),
                  a.reshape(n, -1))

    def mlp(xr):
        hr = _rms(xr, lw["ln2"], eps)
        gate = _mm(hr, lw["w_gate"], precision)
        return _mm(jax.nn.silu(gate) * _mm(hr, lw["w_up"], precision),
                   lw["w_down"], precision)

    return x + _rows(mlp, x)


def loss(cfg: Dict, w: Dict, batch: Dict, precision: str = "f32"):
    """Mean next-token cross entropy of the packed batch (f32 weights)."""
    d = dims(cfg)
    eps = float(cfg["rms_norm_eps"])
    tokens, targets = batch["tokens"], batch["targets"]
    x = w["embed"][tokens]
    for i in range(d["L"]):
        lw = {k: v[i] for k, v in w["layers"].items()}
        x = jax.checkpoint(
            lambda lw_, x_: _layer(cfg, lw_, x_, batch["pos"], batch["seg"],
                                   precision))(lw, x)
    head = w["embed"] if d["tied"] else w["unembed"]

    def ce(xr, tr):
        h = _rms(xr, w["final_norm"], eps)
        logits = _mm(h, head.T, precision)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tl = jnp.take_along_axis(logits, jnp.maximum(tr, 0)[:, None],
                                 axis=-1)[:, 0]
        return jnp.where(tr >= 0, lse - tl, 0.0)

    n = x.shape[0]
    b = _block(n, 4 * d["V"])
    xs = (x.reshape(n // b, b, -1), targets.reshape(n // b, b))
    per_tok = jax.lax.map(lambda a: jax.checkpoint(ce)(*a), xs).reshape(n)
    return jnp.sum(per_tok) / jnp.maximum(jnp.sum(targets >= 0), 1)


def _lr(hp: Dict, step):
    warm = jnp.minimum(step / max(hp["warmup_steps"], 1), 1.0)
    prog = jnp.clip((step - hp["warmup_steps"])
                    / max(hp["total_steps"] - hp["warmup_steps"], 1),
                    0.0, 1.0)
    frac = hp["min_lr_frac"] + (1 - hp["min_lr_frac"]) * 0.5 * (
        1 + jnp.cos(jnp.pi * prog))
    return hp["lr"] * warm * frac


def adamw(hp: Dict, w, g, m, v, step):
    """One decoupled-weight-decay Adam step after clipping the global
    gradient norm; returns (w, m, v, the clipped gradient)."""
    leaves = jax.tree.leaves(g)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in leaves))
    g = jax.tree.map(
        lambda x: x * jnp.minimum(1.0, hp["grad_clip"]
                                  / jnp.maximum(gnorm, 1e-12)), g)
    b1, b2 = hp["beta1"], hp["beta2"]
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    lr = _lr(hp, step)

    def upd(path, p, mm, vv):
        name = jax.tree_util.keystr(path)
        decay = hp["weight_decay"]
        if not hp.get("decay_norm_scales", True) and any(
                f"'{n}'" in name for n in NORM_LEAVES):
            decay = 0.0
        return p - lr * ((mm / c1) / (jnp.sqrt(vv / c2) + hp["eps"])
                         + decay * p)

    return jax.tree_util.tree_map_with_path(upd, w, m, v), m, v, g


def train(cfg: Dict, hp: Dict, seed: int, batches: List[Dict],
          precision: str = "f32", variant: Optional[str] = None,
          device=None) -> Dict:
    """The reference's own steps from the seed's weights, one per batch.

    Returns the loss of every step, and the leaf norms of the first
    clipped gradient, of the master weights' change over all the steps
    and of their compute copies' change."""
    put = (lambda x: jax.device_put(x, device)) if device is not None \
        else jnp.asarray

    made = jax.jit(lambda k: make_weights(cfg, k))
    upcast = jax.jit(lambda t: jax.tree.map(
        lambda x: x.astype(jnp.float32), t))

    def init(key):
        # two programs: the bf16 weights are stored before the upcast, so
        # the compiler cannot keep them unrounded
        return upcast(made(key))

    dtype = jnp.dtype(hp["compute_dtype"])
    with jax.default_device(device) if device is not None else _null():
        key = put(run_key(seed))
        w = init(key)
        m = jax.tree.map(jnp.zeros_like, w)
        v = jax.tree.map(jnp.zeros_like, w)
        bts = [{k: put(np.asarray(x)) for k, x in b.items()}
               for b in batches]
        rounded = jax.jit(lambda t: round_matrices(t, dtype))

        def copy(t):
            return upcast(rounded(t))

        grad = jax.jit(jax.value_and_grad(
            lambda w_, b_: loss(cfg, w_, b_, precision))
        ).lower(w, bts[0]).compile()
        # Adam's moments wait on the host while the gradient is computed
        # when they would not fit the device beside it and the masters
        masters = sum(x.nbytes for x in jax.tree.leaves(w))
        limit = ((device.memory_stats() or {}).get("bytes_limit", 0)
                 if device is not None else 0)
        offload = bool(limit) and (
            grad.memory_analysis().peak_memory_in_bytes + 3 * masters
            > 0.9 * limit)
        step = jax.jit(lambda w_, g_, m_, v_, s_: adamw(hp, w_, g_, m_, v_,
                                                        s_),
                       donate_argnums=(0, 1, 2, 3))
        norms = jax.jit(leaf_norms)
        change = jax.jit(lambda a, b: leaf_norms(
            jax.tree.map(jnp.subtract, a, b)))
        losses, first, wc = [], None, None
        for i, bt in enumerate(bts):
            if wc is None or variant != "unrefreshed":
                wc = copy(w)
            if offload:
                m, v = jax.device_get((m, v))
            lv, g = grad(wc, bt)
            if offload:
                m, v = put(m), put(v)
            w, m, v, g = step(w, g, m, v, jnp.float32(i + 1))
            losses.append(float(lv))
            if first is None:
                first = {k: float(x) for k, x in norms(g).items()}
            del g
        del m, v
        if variant != "unrefreshed":
            wc = copy(w)
        w0 = init(key)
        delta = {k: float(x) for k, x in change(w, w0).items()}
        del w
        copy_delta = {k: float(x) for k, x in change(wc, copy(w0)).items()}
    return {"losses": losses, "grad": first, "delta": delta,
            "copy_delta": copy_delta}


class _null:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False
