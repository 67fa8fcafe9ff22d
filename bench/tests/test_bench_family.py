"""A model family joins the benchmark through new files alone.

A toy family, defined only here, is laid out as a family brings itself:
a configuration naming its program adapter, the adapter in
``bench/configs``, a traffic mix and the cell's limits, under a root
with its own ``BENCHMARK.json``. ``harness.resolve`` loads it, and its
FLOPs are counted by the shared harness through the adapter; an adapter
that is missing, or lacks a function, fails at ``resolve`` and is named.
"""

import json
import shutil
import sys

import pytest

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

    def write(source=TOY_PROGRAM, program="toy_program"):
        cfg = dict(json.loads((DATA / "tiny.json").read_text()),
                   model_type="toy", tie_word_embeddings=False,
                   reference="toy", program=program)
        (cdir / "toy.json").write_text(json.dumps(cfg))
        if source is not None:
            (cdir / f"{program}.py").write_text(source)
            written.append(f"configs.{program}")
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


@pytest.mark.parametrize("missing", ["arch", "to_program", "step_flops"])
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
