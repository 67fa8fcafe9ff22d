"""A model family joins the benchmark through new files alone.

A toy family, defined only here, is laid out as a family brings itself:
a configuration naming its program adapter, the adapter in
``bench/configs``, a traffic mix and the cell's limits, under a root
with its own ``BENCHMARK.json``. ``harness.resolve`` loads it, and its
FLOPs are counted by the shared harness through the adapter; an adapter
that is missing, or lacks a function, fails at ``resolve`` and is named.

A toy family whose layers differ (a leading dense layer, then layers
whose shared and routed experts both hold a ``w_gate``) brings its own
reference as well: the comparison names its leaves through the adapter's
``from_program`` and the reference's ``leaf_norms``, so each is compared
once, under its own name, and a fault in the dense layer alone fails it.
Naming leaves ``layers.<i>.<last key>`` over ``num_hidden_layers`` entries
of every stacked leaf, as the harness did before, would have read the
dense layer's ``w_gate`` as one of the stacked layers' and let one
``w_gate`` overwrite the other.
"""

import json
import shutil
import statistics
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import check
import configs
import harness
from tiny import DATA

# a dense family without qk-norm and with an untied head
TOY_PROGRAM = '''
def arch(cfg):
    from repro.core.plan import ModelSpec
    from repro.models.config import ArchConfig
    spec = ModelSpec(
        name="toy", n_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]),
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]), d_ff=int(cfg["intermediate_size"]),
        vocab=int(cfg["vocab_size"]), qk_norm=False, tie_embeddings=False)
    return ArchConfig(spec=spec, rope_theta=float(cfg["rope_theta"]),
                      rms_eps=float(cfg["rms_norm_eps"]),
                      tie_embeddings=False)


def to_program(w):
    return {"embed": w["embed"], "unembed": w["unembed"]}


def from_program(cfg, tree):
    return tree


def step_flops(cfg, doc_lengths):
    d, f, v, n = (int(cfg[k]) for k in ("hidden_size", "intermediate_size",
                                        "vocab_size", "num_hidden_layers"))
    per_token = n * (4 * d * d + 3 * d * f) + v * d
    return sum(6.0 * per_token * (t - 1) for t in doc_lengths if t > 1)
'''
END_TO_END = [{"name": "tokens_per_s", "unit": "tokens/s",
               "better": "higher", "bound": 0.05, "source": "host_clock"}]
PER_LAYER = [{"name": "step_mfu", "unit": "%", "better": "higher",
              "source": "device_trace", "layer": "train step",
              "moves": "tokens_per_s"}]


@pytest.fixture
def family(tmp_path, monkeypatch):
    """``write(adapter_source, program)`` lays out the toy family under
    ``tmp_path`` and returns the root; its ``bench/configs`` is searched
    as part of the ``configs`` package."""
    cdir = tmp_path / "bench" / "configs"
    cdir.mkdir(parents=True)
    monkeypatch.setattr(configs, "__path__", [*configs.__path__, str(cdir)])
    written = []

    def write(source=TOY_PROGRAM, program="toy_program", reference="toy",
              reference_source=None):
        cfg = dict(json.loads((DATA / "tiny.json").read_text()),
                   model_type="toy", tie_word_embeddings=False,
                   reference=reference, program=program)
        (cdir / "toy.json").write_text(json.dumps(cfg))
        for name, src in ((program, source), (reference, reference_source)):
            if src is not None:
                (cdir / f"{name}.py").write_text(src)
                written.append(f"configs.{name}")
        for sub, src, dst in (("traffic", "tiny-mix", "tiny-mix"),
                              ("limits", "tiny-limits", "toy.tiny-mix")):
            (tmp_path / "bench" / sub).mkdir(exist_ok=True)
            shutil.copy(DATA / f"{src}.json",
                        tmp_path / "bench" / sub / f"{dst}.json")
        (tmp_path / "BENCHMARK.json").write_text(json.dumps({
            "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
            "workloads": [{"name": "toy.tiny-mix", "config": "toy",
                           "traffic": "tiny-mix", "chips": 1}],
            "end_to_end": END_TO_END, "per_layer": PER_LAYER}))
        return tmp_path

    yield write
    for name in written:
        sys.modules.pop(name, None)


def test_toy_family_joins_through_new_files(family):
    cell = harness.resolve("toy.tiny-mix", family())
    assert cell.program.__name__ == "configs.toy_program"
    arch = cell.program.arch(cell.config)
    assert arch.spec.qk_norm is False and arch.spec.d_ff == 128
    # 2 layers of (4 x 64 x 64 + 3 x 64 x 128) and a head of 512 x 64,
    # 6 FLOPs a parameter for each of 4 + 2 targets
    per_token = 2 * (4 * 64 * 64 + 3 * 64 * 128) + 512 * 64
    want = 6.0 * per_token * 6
    win = {"steps": [{"docs": [5, 3, 1], "real": 6, "slots": 8,
                      "bucket": "b", "solve_ms": 1.0}]}
    out = harness.per_layer(cell, win, {"window_s": 1e-3}, None, {}, 0,
                            "TPU v5 lite")
    assert out["step_mfu"]["value"] == pytest.approx(
        100 * want / (1e-3 * 197e12))
    assert set(out) == {"step_mfu"}


@pytest.mark.parametrize("missing", ["arch", "to_program", "from_program",
                                     "step_flops"])
def test_adapter_without_a_function_fails_at_resolve(family, missing):
    source = TOY_PROGRAM.replace(f"def {missing}(", f"def _{missing}(")
    root = family(source, program=f"toy_incomplete_{missing}")
    with pytest.raises(ValueError, match=f"lacks {missing}$"):
        harness.resolve("toy.tiny-mix", root)


@pytest.mark.parametrize("program, message", [
    ("toy_absent", "toy_absent"), ("", "names no program adapter")])
def test_missing_adapter_fails_at_resolve(family, program, message):
    root = family(None, program=program)
    with pytest.raises(ValueError, match=message):
        harness.resolve("toy.tiny-mix", root)


# -- a family whose layers differ ------------------------------------------

# canonical layout: a list of layers, the first dense, the others with two
# shared and EXPERTS routed experts; every leaf named by its path
MIXED_REFERENCE = '''
import jax
import jax.numpy as jnp

EXPERTS = 4


def run_key(seed):
    return jax.random.PRNGKey(seed & 0xFFFFFFFF)


def make_weights(cfg, key, dtype=jnp.bfloat16):
    D, F, V, L = (int(cfg[k]) for k in ("hidden_size", "intermediate_size",
                                        "vocab_size", "num_hidden_layers"))
    keys = iter(jax.random.split(key, 8 * L + 2))

    def mat(*shape):
        return (0.02 * jax.random.normal(next(keys), shape)).astype(dtype)

    def mlp(*lead):
        return {"w_gate": mat(*lead, D, F), "w_up": mat(*lead, D, F),
                "w_down": mat(*lead, F, D)}

    dense = {"ln": jnp.ones((D,), dtype), "mlp": mlp()}
    moe = [{"ln": jnp.ones((D,), dtype), "router": mat(D, EXPERTS),
            "shared": mlp(), "experts": mlp(EXPERTS)} for _ in range(L - 1)]
    return {"embed": mat(V, D), "final_norm": jnp.ones((D,), dtype),
            "layers": [dense, *moe]}


def leaf_norms(w):
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(w)[0]:
        name = ".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        out[name] = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
    return out
'''

# program layout: the dense layer apart, the MoE layers stacked [L - 1, ...]
# (which ``stack`` below pads to stages, as the program would), the
# vocabulary padded to a multiple of VOCAB_PAD
MIXED_PROGRAM = '''
import jax

D_P, VOCAB_PAD = 2, 8


def arch(cfg):
    raise NotImplementedError("no program runs this toy")


def step_flops(cfg, doc_lengths):
    return 0.0


def to_program(w):
    dense, *moe = w["layers"]
    return {"embed": w["embed"], "final_norm": w["final_norm"],
            "dense": dense,
            "stages": jax.tree.map(lambda *xs: jax.numpy.stack(xs), *moe)}


def from_program(cfg, tree):
    n, v = int(cfg["num_hidden_layers"]) - 1, int(cfg["vocab_size"])
    stages = jax.tree.map(lambda x: x.reshape(-1, *x.shape[2:])[:n],
                          tree["stages"])
    moe = [jax.tree.map(lambda x: x[i], stages) for i in range(n)]
    return {"embed": tree["embed"][:v], "final_norm": tree["final_norm"],
            "layers": [tree["dense"], *moe]}
'''


def stack(raw, d_p=2, vocab_pad=8):
    """What the program would make of ``to_program``'s tree: the MoE
    layers padded to fill ``d_p`` stages and stacked ``[d_p, L_s, ...]``,
    the vocabulary padded to a multiple of ``vocab_pad``."""
    def stages(x):
        l_s = -(-x.shape[0] // d_p)
        x = jnp.concatenate([x, jnp.zeros((d_p * l_s - x.shape[0],
                                           *x.shape[1:]), x.dtype)])
        return x.reshape(d_p, l_s, *x.shape[1:])

    embed = raw["embed"]
    pad = (-embed.shape[0]) % vocab_pad
    return dict(raw, stages=jax.tree.map(stages, raw["stages"]),
                embed=jnp.concatenate([embed, jnp.zeros(
                    (pad, *embed.shape[1:]), embed.dtype)]))


def _mixed(family, program="toy_mixed_program", source=MIXED_PROGRAM):
    root = family(source, program=program, reference="toy_mixed",
                  reference_source=MIXED_REFERENCE)
    cell = harness.resolve("toy.tiny-mix", root)
    # a leading dense layer and 3 MoE layers: 2 stages of 2, one padded
    cell.config["num_hidden_layers"] = 4
    cell.config["vocab_size"] = 509
    return cell


def _grad(cell, seed):
    """A gradient-like tree in the reference's layout, float32."""
    return jax.tree.map(lambda x: x.astype(jnp.float32),
                        cell.reference.make_weights(
                            cell.config, cell.reference.run_key(seed)))


def test_mixed_family_names_every_leaf_once(family):
    cell = _mixed(family)
    g = _grad(cell, 3)
    want = cell.reference.leaf_norms(g)
    got = harness.leaf_norms(cell)(stack(cell.program.to_program(g)))
    assert len(got) == len(jax.tree.leaves(g)) == len(want)
    assert set(got) == set(want)
    assert {"layers.0.mlp.w_gate", "layers.1.shared.w_gate",
            "layers.1.experts.w_gate", "layers.3.experts.w_gate"} <= set(got)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-6)
    harness.check_layout(cell, jax.eval_shape(
        lambda t: stack(cell.program.to_program(t)), g))


def test_mixed_family_fault_in_dense_w_gate_fails_grad_gap(family):
    cell = _mixed(family)
    g = _grad(cell, 4)
    # new containers: to_program's tree shares the reference's dicts
    prog = jax.tree.map(lambda x: x, stack(cell.program.to_program(g)))
    prog["dense"]["mlp"]["w_gate"] = 0.8 * prog["dense"]["mlp"]["w_gate"]
    ref = {k: float(v) for k, v in cell.reference.leaf_norms(g).items()}
    got = {k: float(v) for k, v in harness.leaf_norms(cell)(prog).items()}
    readings = {"losses": [1.0], "grad": got, "delta": ref,
                "copy_delta": ref, "tokens_gap": 0}
    verdict = check.verdict(check.numbers(readings, {
        "losses": [1.0], "grad": ref, "delta": ref, "copy_delta": ref}),
        cell.limits)
    assert not verdict["grad_gap"]["ok"], verdict
    n = ref["layers.0.mlp.w_gate"]
    assert verdict["grad_gap"]["value"] == pytest.approx(
        0.2 * n / max(n, statistics.median(ref.values())), rel=1e-5)
    assert all(v["ok"] for k, v in verdict.items() if k != "grad_gap")


@pytest.mark.parametrize("fault, old, new", [
    ("dropped", '"final_norm": tree["final_norm"],', ""),
    ("padded", 'tree["embed"][:v]', 'tree["embed"]'),
    ("counted_twice", '[tree["dense"], *moe]', '[tree["dense"], *moe, moe[0]]'),
])
def test_layout_guard_names_both_counts(family, fault, old, new):
    assert old in MIXED_PROGRAM
    cell = _mixed(family, program=f"toy_mixed_{fault}",
                  source=MIXED_PROGRAM.replace(old, new))
    abstract = jax.eval_shape(lambda t: stack(cell.program.to_program(t)),
                              _grad(cell, 5))
    with pytest.raises(ValueError, match=r"gives back \d+ elements; "
                       r"to_program's tree holds \d+"):
        harness.check_layout(cell, abstract)


# -- Qwen3's adapter ---------------------------------------------------------

@pytest.mark.parametrize("layers, mesh, tied", [
    (2, (1, 1), True), (3, (2, 3), True), (3, (2, 3), False)])
def test_qwen3_from_program_inverts_the_program_layout(layers, mesh, tied):
    """On differences of two seeds' bf16 weights, as the check reads them,
    so that RMSNorm's offset cancels: exact, leaf by leaf. (3 layers over 2
    stages pad one layer; a model axis of 3 pads the vocabulary, of the
    untied head too.)"""
    from repro.runtime.train_step import prepare_params
    from configs import qwen3, qwen3_program
    cfg = dict(json.loads((DATA / "tiny.json").read_text()),
               num_hidden_layers=layers, tie_word_embeddings=tied)
    arch = qwen3_program.arch(cfg)
    m = jax.sharding.AbstractMesh(mesh, ("data", "model"))

    def P(w):
        return prepare_params(arch, qwen3_program.to_program(w), m,
                              jnp.bfloat16, v_stages=1)

    a, b = (qwen3.make_weights(cfg, qwen3.run_key(s)) for s in (1, 2))
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    diff = jax.tree.map(lambda x, y: f32(x) - f32(y), P(a), P(b))
    got = qwen3_program.from_program(cfg, diff)
    want = jax.tree.map(lambda x, y: f32(x) - f32(y), a, b)
    assert ("unembed" in got) == (not tied)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(x, y)
    assert set(qwen3.leaf_norms(got)) == set(qwen3.leaf_norms(a))
