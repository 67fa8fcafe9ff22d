"""The program's named scopes and host spans, and the reduction that reads
them (``bench/scopes.py``): the compiled step carries every layer's name
in its op metadata, the names change no number the step computes, the
``repro.*`` spans reach the profiler's trace without changing what
``devtrace`` reads from it, and device time is attributed by hand-counted
rules."""

import contextlib
import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

import numpy as np
import pytest

import configs
import devtrace
import scopes
import steppath
from tiny import DATA

BENCH = Path(__file__).resolve().parents[1]


# -- the compiled step ------------------------------------------------------

def _arch():
    cfg = json.loads((DATA / "tiny.json").read_text())
    return configs.program(cfg).arch(cfg)


def _compiled_step(mesh_shape=(1, 1), l_ckpt=1, **geom):
    """(builder, compiled step) of the tiny Qwen3 at 4 chunks of 128."""
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_mesh
    from repro.optim import init_opt_state
    from repro.runtime import TrainStepBuilder, batch_struct, make_geometry
    arch = _arch()
    mesh = make_mesh(mesh_shape, ("data", "model"))
    g = make_geometry(arch, mesh, n_chunks=4, cap=128, ctx_cap=128,
                      l_ckpt=l_ckpt, compute_dtype=jnp.bfloat16, **geom)
    builder = TrainStepBuilder(arch, mesh, g, param_dtype=jnp.bfloat16)
    params = builder.abstract_params()
    opt = jax.eval_shape(init_opt_state, params)
    step = builder.build(params).lower(
        params, opt, None, batch_struct(g, 1)).compile()
    return builder, step


def _layers(step):
    (ops,) = scopes.scope_map(step).values()
    return {scopes.layer_of(v) for v in ops.values()}, ops


def test_step_names_its_layers():
    _, step = _compiled_step(l_ckpt=1)
    layers, ops = _layers(step)
    assert {"attention", "mlp", "ce_head", "embed", "optimizer",
            "stage_tick"} <= layers
    assert any(scopes.REMAT in v.split("/") for v in ops.values())


COLLECTIVES = r"""
import json, re, sys
sys.path[:0] = [{bench!r}, {tests!r}]
import scopes, test_bench_scopes as t
_, step = t._compiled_step((2, 2), l_ckpt=0, sp_policy="allgather_kv",
                           sp_degree=2)
(ops,) = scopes.scope_map(step).values()
kinds = re.findall(r"%(\S+) = \S+ (collective-permute|all-gather)\(",
                   step.as_text())
print(json.dumps([[kind, ops.get(name, "")] for name, kind in kinds]))
"""


def test_collectives_carry_handoff_and_sp_scopes():
    """2x2 mesh of virtual devices: 2 stages, KV all-gathered over 2."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = COLLECTIVES.format(bench=str(BENCH), tests=str(BENCH / "tests"))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    ops = json.loads(p.stdout.strip().splitlines()[-1])
    permutes = [v for k, v in ops if k == "collective-permute"]
    gathers = [v for k, v in ops if k == "all-gather"]
    assert permutes and all(scopes.layer_of(v) == "handoff"
                            for v in permutes)
    assert any(scopes.layer_of(v) == "sp_collective" for v in gathers)


def _tiny_batch(n=4, cap=128, vocab=512, seed=0):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, vocab, (n, cap)).astype(np.int32)
    return {"tokens": jnp.asarray(tokens),
            "targets": jnp.asarray(np.roll(tokens, -1, axis=1)),
            "seg": jnp.zeros((n, cap), jnp.int32),
            "pos": jnp.asarray(np.tile(np.arange(cap, dtype=np.int32),
                                       (n, 1))),
            "ctx_len": jnp.zeros((n,), jnp.int32)}


def test_scopes_change_no_number(monkeypatch):
    """One step with the scopes and one built with ``named_scope`` made a
    no-op: the loss, the new parameters and the optimizer state (the
    first gradient in ``m``) are bitwise equal."""
    import jax

    def run(step, builder):
        params, opt, _ = builder.init_all(jax.random.PRNGKey(5))
        return jax.device_get(step(params, opt, None, _tiny_batch()))

    builder, scoped = _compiled_step(l_ckpt=1)
    with_scopes = run(scoped, builder)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    builder, plain = _compiled_step(l_ckpt=1)
    assert _layers(plain)[0] == {"unscoped"}
    without = run(plain, builder)
    a, b = jax.tree.leaves(with_scopes), jax.tree.leaves(without)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes()


# -- the program's host spans -------------------------------------------------

def test_program_spans_leave_devtrace_as_it_was(tmp_path):
    """A CPU profile of planning, a cold compile, a store load and the
    feed holds the ``repro.*`` spans; ``devtrace`` reads the same host
    spans and the same numbers from it as from a trace without them."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.data import sample_corpus_batch
    from repro.launch.mesh import make_mesh
    arch = _arch()
    mesh = make_mesh((1, 1), ("data", "model"))
    corpus = sample_corpus_batch("github", 2, 128, arch.spec.vocab, seed=3)
    store = str(tmp_path / "store")
    with tempfile.TemporaryDirectory() as tdir:
        jax.profiler.start_trace(tdir)
        with TraceAnnotation("window"):
            cold = steppath.StepPath(arch, mesh, store, log=lambda m: None)
            with TraceAnnotation("plan"):
                plan = cold.solve([len(v) for v in corpus.values()])
            cold.get_step(plan)
            warm = steppath.StepPath(arch, mesh, store, log=lambda m: None)
            warm.get_step(plan)
            warm.materialize(plan, corpus)
        jax.profiler.stop_trace()
        full, bare = scopes.load(tdir), devtrace.load(tdir)
    assert (cold.cache.stats.misses, warm.cache.stats.warm_hits) == (1, 1)
    names = {s[0] for s in full["spans"]}
    assert {"repro.plan_batch", "repro.compile", "repro.store_load",
            "repro.materialize"} <= names
    (w0, wd), = [(s, d) for n, s, d in full["host"] if n == "window"]
    assert all(w0 <= s and s + d <= w0 + wd for _, s, d in full["spans"])
    assert full["host"] == bare["host"]
    assert {h[0] for h in bare["host"]} == {"window", "plan"}
    ops = {"TPU:0": [["fusion.1", w0 + 0.1 * wd, 0.2 * wd],
                     ["fusion.2", w0 + 0.5 * wd, 0.1 * wd]]}
    assert devtrace.reduce(dict(full, devices=ops), 1) == \
        devtrace.reduce(dict(bare, devices=ops), 1)


# -- the reduction, by hand ---------------------------------------------------

STEP = "jit(step)"
MAPS = {
    "jit_step": {
        "fusion.1": f"{STEP}/jvp()/stage_tick/attention/dot_general",
        "fusion.2": f"{STEP}/transpose(jvp())/stage_tick/checkpoint/"
                    f"rematted_computation/attention/sp_collective/"
                    f"all_gather",
        "fusion.3": f"{STEP}/ce_head/dot_general",
        "copy.4": f"{STEP}/while/body/dynamic_update_slice",
        "while.5": f"{STEP}/while",
        "fusion.6": f"{STEP}/transpose(jvp(mlp))/dot_general",
    },
    "jit_other": {"fusion.1": "jit(other)/optimizer/add"},
}
# two chips, a window of 200 ns from t=1000; both run jit_step, then
# jit_other; chip 1 runs a module with no map in between
MODULES = [["jit_step(11)", 1000.0, 140.0], ["jit_other(12)", 1150.0, 50.0]]
HAND = {
    "host": [["window", 1000.0, 200.0], ["plan", 1000.0, 10.0]],
    "modules": {"TPU:0": MODULES,
                "TPU:1": MODULES + [["jit_unknown(13)", 1140.0, 10.0]]},
    "devices": {
        "TPU:0": [["%fusion.1 = bf16[8] fusion(...)", 990.0, 30.0],
                  ["fusion.2", 1020.0, 40.0],
                  ["while.5", 1000.0, 140.0],
                  ["fusion.3", 1060.0, 30.0],
                  ["copy.4", 1090.0, 10.0],
                  ["fusion.6", 1100.0, 20.0],
                  ["fusion.1", 1160.0, 20.0],
                  ["fusion.9", 1300.0, 10.0]],
        "TPU:1": [["fusion.1", 1000.0, 40.0],
                  ["fusion.3", 1040.0, 20.0],
                  ["fusion.7", 1120.0, 20.0],
                  ["fusion.1", 1140.0, 10.0]],
    },
}


def test_hand_counted_reduction():
    r = scopes.reduce(HAND, MAPS, n_steps=2)
    # chip 0: fusion.1 clipped to 20 (attention), fusion.2 40 (the
    # innermost scope: sp_collective; backward, recomputed), the while
    # left out, fusion.3 30 (ce_head), copy.4 10 (unscoped), fusion.6 20
    # (mlp, backward), fusion.1 of jit_other 20 (optimizer), fusion.9
    # outside the window. Chip 1: attention 40, ce_head 20, fusion.7 not
    # in the map 20 (unscoped), fusion.1 of the unknown module 10
    # (unscoped, unmapped). Per chip and step: / 2 / 2, ns -> ms.
    ms = 1e-6 / 4
    want = {"attention": 60, "sp_collective": 40, "ce_head": 50,
            "mlp": 20, "optimizer": 20, "unscoped": 40, "embed": 0,
            "handoff": 0, "stage_tick": 0}
    assert r["layers_ms"] == pytest.approx({k: v * ms
                                            for k, v in want.items()})
    assert r["leaf_ms"] == pytest.approx(230 * ms)
    assert sum(r["layers_ms"].values()) == pytest.approx(r["leaf_ms"])
    assert r["backward_ms"]["sp_collective"] == pytest.approx(40 * ms)
    assert r["backward_ms"]["mlp"] == pytest.approx(20 * ms)
    assert r["backward_ms"]["attention"] == 0
    assert r["remat_ms"] == pytest.approx(40 * ms)
    assert r["unmapped_ms"] == pytest.approx(10 * ms)
    assert r["scoped"]


def test_one_map_serves_a_trace_without_modules():
    trace = dict(HAND, modules={})
    r = scopes.reduce(trace, {"jit_step": MAPS["jit_step"]}, n_steps=1)
    # every op is read in jit_step's map: chip 0's late fusion.1 and chip
    # 1's fusion.1 are attention now, fusion.7 stays unscoped
    assert r["layers_ms"]["attention"] == pytest.approx(
        (20 + 20 + 40 + 10) * 1e-6 / 2)
    assert r["unmapped_ms"] == 0


def test_a_program_without_scopes_reads_unscoped():
    bare = {m: {k: v.replace("attention", "x").replace("mlp", "y")
                .replace("ce_head", "z").replace("optimizer", "w")
                .replace("sp_collective", "v").replace("stage_tick", "u")
                for k, v in ops.items()} for m, ops in MAPS.items()}
    r = scopes.reduce(HAND, bare, n_steps=2)
    assert not r["scoped"]
    assert r["layers_ms"]["unscoped"] == pytest.approx(r["leaf_ms"])
    from metrics import reader
    for m in ("ce_head_ms", "attention_ms", "mlp_ms", "recompute_share"):
        assert reader(m)({"scopes": r}) is None
        assert reader(m)({}) is None


def test_metrics_read_the_reduction():
    from metrics import reader
    r = scopes.reduce(HAND, MAPS, n_steps=2)
    run = {"scopes": r}
    assert reader("ce_head_ms")(run) == pytest.approx(50e-6 / 4)
    assert reader("attention_ms")(run) == pytest.approx(60e-6 / 4)
    assert reader("mlp_ms")(run) == pytest.approx(20e-6 / 4)
    assert reader("recompute_share")(run) == pytest.approx(100 * 40 / 230)


@pytest.mark.parametrize("path, layer", [
    ("jit(f)/transpose(jvp(stage_tick))/jvp(stage_tick)/checkpoint/"
     "rematted_computation/attention/dot_general", "attention"),
    ("jit(f)/stage_tick/attention/sp_collective/all_gather",
     "sp_collective"),
    ("jit(f)/jvp(stage_tick)/ce_head/checkpoint/dot_general", "ce_head"),
    ("jit(f)/jvp(mlp)/mul", "mlp"),
    ("jit(f)/jit(embed_rows)/add", "unscoped"),
    ("params['embed']", "unscoped"),
    ("", "unscoped"),
])
def test_layer_of(path, layer):
    assert scopes.layer_of(path) == layer


class _Text:
    def __init__(self, text):
        self.text = text

    def as_text(self):
        return self.text


HLO = r"""HloModule jit_step, is_scheduled=true, entry_computation_layout={()}

%fused_computation (param_0.2: f32[4]) -> f32[4] {
  %param_0.2 = f32[4]{0} parameter(0)
  ROOT %sin.0 = f32[4]{0} sine(%param_0.2), metadata={op_name="jit(step)/mlp/sin" stack_frame_id=2}
}

ENTRY %main.1 (x.1: f32[4]) -> f32[4] {
  %x.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  %constant.3 = f32[] constant(1)
  ROOT %sine_fusion.12 = f32[4]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/transpose(jvp())/attention/batch[\'seg\']/sin" source_file="a.py"}
}
"""


def test_scope_map_reads_the_module_text():
    m = scopes.scope_map(_Text(HLO))
    assert list(m) == ["jit_step"]
    assert m["jit_step"] == {
        "sin.0": "jit(step)/mlp/sin", "x.1": "x",
        "sine_fusion.12":
            r"jit(step)/transpose(jvp())/attention/batch[\'seg\']/sin"}
    with pytest.raises(ValueError):
        scopes.scope_map(_Text("not a module"))
    assert scopes.module_name("jit_step(1234)") == "jit_step"


# -- scopes declared by readers ---------------------------------------------

def test_scopes_are_the_readers_and_the_closing_ones():
    import metrics
    assert set(scopes.SCOPES) == set(metrics.declared_scopes()) | set(
        scopes.CLOSING)
    assert len(scopes.SCOPES) == len(set(scopes.SCOPES)) == 8
    # attention, mlp and ce_head are counted because a reader reads them
    assert {"attention", "mlp", "ce_head"} <= set(metrics.declared_scopes())
    assert not {"attention", "mlp", "ce_head"} & set(scopes.CLOSING)


def test_a_new_reader_declares_a_new_scope(tmp_path):
    """A reader file that declares ``SCOPE = "moe"`` makes ``moe`` a
    counted scope, with no edit: an op under it is no longer
    ``unscoped``."""
    import importlib

    import metrics
    (tmp_path / "moe_ms.py").write_text(
        'SCOPE = "moe"\n\n\ndef read(run):\n'
        '    return run["scopes"]["layers_ms"][SCOPE]\n')
    path = list(metrics.__path__)
    metrics.__path__.append(str(tmp_path))
    importlib.invalidate_caches()
    try:
        assert "moe" in metrics.declared_scopes()
        importlib.reload(scopes)
        path_ = f"{STEP}/stage_tick/moe/dot_general"
        assert scopes.layer_of(path_) == "moe"
        maps = {"jit_step": dict(MAPS["jit_step"], **{"fusion.7": path_})}
        r = scopes.reduce(HAND, maps, n_steps=2)
        assert r["layers_ms"]["moe"] == pytest.approx(20e-6 / 4)
        assert sum(r["layers_ms"].values()) == pytest.approx(r["leaf_ms"])
        assert metrics.reader("moe_ms")({"scopes": r}) == pytest.approx(
            20e-6 / 4)
    finally:
        metrics.__path__[:] = path
        sys.modules.pop("metrics.moe_ms", None)
        importlib.reload(scopes)
    assert "moe" not in scopes.SCOPES


def test_optimizer_and_head_roofline_read_the_reduction():
    from metrics import reader
    r = scopes.reduce(HAND, MAPS, n_steps=2)
    cfg = {"hidden_size": 4, "vocab_size": 10}
    steps = [{"docs": [4, 1]}, {"docs": [3]}]
    run = {"scopes": r, "config": cfg, "steps": steps, "chips": 2,
           "peak_flops": 1e9, "peak_bytes_per_s": 1e9}
    assert reader("optimizer_ms")(run) == pytest.approx(20e-6 / 4)
    # head: 6 x 4 x 10 x (3 + 2) targets = 1200 FLOP over 2 chips x 1e9;
    # least bytes 3 x 2 x 40 = 240 a step, 2 steps, over 2 x 1e9: the
    # FLOPs bound it. The ce_head scope: 50 ns over 2 chips and 2 steps
    # -> 12.5 ns a step and chip, 25 ns a chip for the 2 steps.
    assert reader("ce_head_roofline")(run) == pytest.approx(
        100 * (1200 / 2e9) / 25e-9)
    run["peak_bytes_per_s"] = 1e6     # the bytes bound it now
    assert reader("ce_head_roofline")(run) == pytest.approx(
        100 * (480 / 2e6) / 25e-9)
    for name in ("optimizer_ms", "ce_head_roofline"):
        assert reader(name)(dict(run, scopes=None)) is None
    no_head = dict(r, layers_ms=dict(r["layers_ms"], ce_head=0.0))
    assert reader("ce_head_roofline")(dict(run, scopes=no_head)) is None
