"""Launch-environment contracts: the chip smoke refuses a CPU, the compile
cache lives where the environment or the checkout says, meshes use Auto
axes, device peaks come from a keyed table, and a tied head on the
embedding's own stage is counted once."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.core import ClusterSpec, CostModel
from repro.core.plan import DEVICE_PEAKS
from repro.launch import mesh as mesh_mod

ROOT = Path(__file__).resolve().parents[1]


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr


def test_compile_cache_dir_env_and_default(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/cache")
    assert mesh_mod.compile_cache_dir() == "/somewhere/cache"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = Path(mesh_mod.compile_cache_dir())
    assert path == ROOT / ".jax_cache"
    # a fixed path: no pid, temp name or time stamp in it
    assert str(os.getpid()) not in str(path)
    assert mesh_mod.compile_cache_dir() == str(path)


def test_make_mesh_uses_auto_axes():
    import jax
    from jax.sharding import AxisType
    m = mesh_mod.make_mesh((1, 1), ("data", "model"))
    assert m.axis_types == (AxisType.Auto, AxisType.Auto)
    assert m.devices.flat[0] == jax.devices()[0]


def test_cluster_spec_for_device():
    v5e = ClusterSpec.for_device("tpu", "TPU v5 lite", d_p=2, d_s=2)
    assert (v5e.flops_per_chip, v5e.hbm_bytes) == (197e12, 16e9)
    assert (v5e.d_p, v5e.d_s) == (2, 2)
    cpu = ClusterSpec.for_device("cpu", "cpu", d_p=1, d_s=1)
    assert cpu.hbm_bytes == DEVICE_PEAKS["cpu"]["hbm_bytes"]
    with pytest.raises(ValueError, match="no peak numbers"):
        ClusterSpec.for_device("tpu", "TPU v99", d_p=1, d_s=1)


@pytest.mark.parametrize("d_p", [1, 2])
def test_tied_head_counted_once_per_stage(d_p):
    from repro.configs import get_arch
    spec = get_arch("llama3.2-3b").spec
    cm = CostModel(spec, ClusterSpec(d_p=d_p, d_s=1))
    emb = spec.vocab * spec.d_model * 18       # bytes/param of model states
    body = (spec.param_count() - spec.vocab * spec.d_model) * 18 / d_p
    # the tied head is materialized on the last stage; at d_p == 1 that
    # stage IS the embedding's, which holds it once
    assert cm.m_model_states(d_p) == pytest.approx(body + emb)


def test_train_raises_remat_when_the_compiler_refuses(monkeypatch):
    """A step the compiler refuses for memory is re-planned with a remat
    floor past the refused plan's depth, and the run goes on."""
    import jax

    from repro.configs import get_arch
    from repro.launch.mesh import make_mesh
    from repro.launch.train import TrainLoopConfig, train

    real = jax.stages.Lowered.compile
    calls = []

    def refuse_first(self, *a, **k):
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("RESOURCE_EXHAUSTED: Ran out of memory in "
                               "memory space hbm.")
        return real(self, *a, **k)
    monkeypatch.setattr(jax.stages.Lowered, "compile", refuse_first)
    logs = []
    _, _, hist = train(get_arch("gemma3-1b").reduced(),
                       make_mesh((1, 1), ("data", "model")),
                       TrainLoopConfig(steps=1, global_batch=2, context=128,
                                       compute_dtype="float32"),
                       log=logs.append)
    assert any("re-planning with remat depth >= 1" in m for m in logs)
    compiled = [m for m in logs if m.startswith("[compile:train-step]")]
    assert compiled and all("ckpt='u1'" in m for m in compiled)
    assert len(hist) == 1 and hist[0]["loss"] > 0
