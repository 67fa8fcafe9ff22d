"""The program adapter of the Qwen3 family (``bench/configs/__init__.py``):
its ``ArchConfig``, its parameter tree from the reference's canonical
weights (``qwen3.make_weights``) and back, and its model FLOPs."""

from __future__ import annotations

from typing import Dict, Iterable


def arch(cfg: Dict):
    """The program's ``ArchConfig`` for a Qwen3 configuration file."""
    from repro.core.plan import ModelSpec
    from repro.models.config import ArchConfig
    if cfg["model_type"] != "qwen3":
        raise ValueError(f"not a Qwen3 configuration: {cfg['model_type']!r}")
    tied = bool(cfg["tie_word_embeddings"])
    spec = ModelSpec(
        name=f"qwen3-d{cfg['hidden_size']}-l{cfg['num_hidden_layers']}",
        n_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]),
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]), d_ff=int(cfg["intermediate_size"]),
        vocab=int(cfg["vocab_size"]), qk_norm=True, tie_embeddings=tied)
    return ArchConfig(spec=spec, rope_theta=float(cfg["rope_theta"]),
                      rms_eps=float(cfg["rms_norm_eps"]),
                      tie_embeddings=tied)


def to_program(w: Dict) -> Dict:
    """Canonical weights -> the program's parameter tree. The program's
    RMSNorm multiplies by (1 + gamma), so its scales are the canonical
    scales minus 1."""
    L = w["layers"]
    raw = {"embed": w["embed"], "final_norm": w["final_norm"] - 1,
           "layers": {"ln1": L["ln1"] - 1, "ln2": L["ln2"] - 1,
                      "attn": {"wq": L["wq"], "wk": L["wk"], "wv": L["wv"],
                               "wo": L["wo"], "q_norm": L["q_norm"] - 1,
                               "k_norm": L["k_norm"] - 1},
                      "mlp": {"w_gate": L["w_gate"], "w_up": L["w_up"],
                              "w_down": L["w_down"]}}}
    if "unembed" in w:
        raw["unembed"] = w["unembed"]
    return raw


def from_program(cfg: Dict, tree: Dict) -> Dict:
    """A tree of the program's parameter layout (parameters, AdamW's
    moments, or a difference of two) in the canonical layout: the inverse
    of ``to_program`` followed by ``prepare_params`` at ``v_stages=1``.
    The stage-stacked layers ``[d_p, L_s, ...]`` become ``[L, ...]`` less
    the layers that pad the last stage, and the embedding and head lose
    the rows that pad the vocabulary. No value changes: a program scale is
    the canonical one minus 1, which a difference cancels."""
    n, v = int(cfg["num_hidden_layers"]), int(cfg["vocab_size"])
    S = tree["stages"]

    def layers(x):
        return x.reshape(-1, *x.shape[2:])[:n]

    A, M = S["attn"], S["mlp"]
    out = {"embed": tree["embed"][:v], "final_norm": tree["final_norm"],
           "layers": {"ln1": layers(S["ln1"]), "ln2": layers(S["ln2"]),
                      "wq": layers(A["wq"]), "wk": layers(A["wk"]),
                      "wv": layers(A["wv"]), "wo": layers(A["wo"]),
                      "q_norm": layers(A["q_norm"]),
                      "k_norm": layers(A["k_norm"]),
                      "w_gate": layers(M["w_gate"]),
                      "w_up": layers(M["w_up"]),
                      "w_down": layers(M["w_down"])}}
    if "unembed" in tree:
        out["unembed"] = tree["unembed"][:v]
    return out


def matmul_params(cfg: Dict) -> int:
    """Parameters of every matrix product a token passes through: each
    layer's q, k, v, o projections and SwiGLU MLP, and the output head."""
    d, hq, hkv, dh, f, v, n = (int(cfg[k]) for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "intermediate_size", "vocab_size", "num_hidden_layers"))
    per_layer = d * hq * dh + 2 * d * hkv * dh + hq * dh * d + 3 * d * f
    return n * per_layer + v * d


def step_flops(cfg: Dict, doc_lengths: Iterable[int]) -> float:
    """Model FLOPs of a step over documents of ``doc_lengths`` tokens, as
    ``bench/flops.py`` defines them."""
    hq, dh, n_layers = (int(cfg[k]) for k in (
        "num_attention_heads", "head_dim", "num_hidden_layers"))
    p = matmul_params(cfg)
    total = 0.0
    for n in doc_lengths:
        t = n - 1
        if t <= 0:
            continue
        pairs = t * (t + 1) / 2
        total += 6.0 * p * t + 3 * 2 * 2 * pairs * hq * dh * n_layers
    return total
