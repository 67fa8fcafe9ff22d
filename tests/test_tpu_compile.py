"""Compile-only checks of the Pallas kernels for a TPU v5e at real widths.

The TPU compiler is installed with jaxlib and compiles for a chip that is
described, not attached (``jax.experimental.topologies``). Each case lowers
one kernel at a production model's widths, compiles it for one v5e chip
and asserts that the Mosaic kernel (``tpu_custom_call``) is in the program:
the scoped-VMEM and tiling limits interpret mode cannot see are checked here.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, and every test worker imports
this file. The persistent compilation cache is off around these compiles
(a compile for a described chip cannot be read back without the chip).
"""

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.kernels import ops  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("T,Hq,Hkv,Dh,window", [
    (4096, 24, 8, 128, 0),     # llama3.2-3b
    (4096, 4, 1, 256, 512),    # gemma3-1b sliding-window layer
])
def test_flash_attention_compiles(one_chip, T, Hq, Hkv, Dh, window):
    def fn(q, k, v, seg, pos):
        return ops.flash_attention(q, k, v, seg, seg, pos, pos,
                                   window=window)
    text = _compile_text(fn, one_chip,
                         ((T, Hq, Dh), jnp.bfloat16),
                         ((T, Hkv, Dh), jnp.bfloat16),
                         ((T, Hkv, Dh), jnp.bfloat16),
                         ((T,), jnp.int32), ((T,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("grad", [False, True])
def test_fused_cross_entropy_compiles(one_chip, grad):
    """llama3.2-3b head: D=3072, V=128256 (forward; forward + both
    backward kernels)."""
    T, D, V = 2048, 3072, 128256

    def loss(h, w, tgt, valid):
        s, n = ops.fused_cross_entropy(h, w, tgt, valid)
        return s / n
    fn = jax.grad(loss, argnums=(0, 1)) if grad else loss
    text = _compile_text(fn, one_chip,
                         ((T, D), jnp.bfloat16), ((V, D), jnp.bfloat16),
                         ((T,), jnp.int32), ((T,), jnp.bool_))
    assert text.count("tpu_custom_call") >= (3 if grad else 1)


def test_mamba_scan_compiles(one_chip):
    """falcon-mamba-7b: d_inner 8192, d_state 16."""
    T, DI, DS = 1024, 8192, 16

    def fn(delta, xs, B, C, A, reset, h0):
        return ops.mamba_scan(delta, xs, B, C, A, reset, h0)
    text = _compile_text(fn, one_chip,
                         ((T, DI), jnp.bfloat16), ((T, DI), jnp.bfloat16),
                         ((T, DS), jnp.bfloat16), ((T, DS), jnp.bfloat16),
                         ((DI, DS), jnp.float32), ((T,), jnp.int32),
                         ((DI, DS), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_train_step_fits_v5e(topo, mesh_shape):
    """``chip_smoke.py``'s step: llama3.2-3b at every published width cut
    to 2 layers, the github batch of 2 at context 8192, planned for a v5e
    by the default planner. The compiled step fits the chip, and the
    planner's estimate of its memory (``CostModel.m_step``) is within 10%
    of the compiler's peak."""
    import dataclasses

    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_arch
    from repro.core import (ClusterSpec, CostModel, PlannerConfig,
                            plan_batch)
    from repro.data import sample_corpus_batch
    from repro.launch.mesh import make_mesh
    from repro.optim import init_opt_state
    from repro.runtime import TrainStepBuilder, batch_struct, make_geometry

    cfg = get_arch("llama3.2-3b")
    cfg = dataclasses.replace(
        cfg, spec=dataclasses.replace(cfg.spec, n_layers=2))
    d_p, d_s = mesh_shape
    mesh = make_mesh(mesh_shape, ("data", "model"),
                     devices=topo.devices[:d_p * d_s])
    cm = CostModel(cfg.spec, ClusterSpec.for_device(
        "tpu", "TPU v5 lite", d_p=d_p, d_s=d_s))
    corpus = sample_corpus_batch("github", 2, 8192, cfg.spec.vocab, seed=2)
    plan = plan_batch(cm, [len(v) for v in corpus.values()],
                      PlannerConfig(bucket_rounding=256,
                                    remat_mode="stage_aware"))
    key = plan.bucket_key(d_s)
    l_max, table, _ = plan.ckpt_policy(key.n_chunks)
    geom = make_geometry(cfg, mesh, n_chunks=key.n_chunks, cap=key.cap,
                         ctx_cap=key.ctx_cap, l_ckpt=l_max,
                         compute_dtype=jnp.bfloat16, schedule=key.schedule,
                         v_stages=key.v_stages, ckpt_table=table,
                         sp_policy=key.sp_policy, sp_degree=key.d_s_eff)
    builder = TrainStepBuilder(cfg, mesh, geom, param_dtype=jnp.bfloat16)
    params = builder.abstract_params()
    pspecs, ospecs, bspecs = builder.specs(params)

    def placed(tree, specs):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
            tree, specs, is_leaf=lambda x: isinstance(x, P))
    compiled = builder.build(params).lower(
        placed(params, pspecs),
        placed(jax.eval_shape(init_opt_state, params), ospecs), None,
        placed(batch_struct(geom, 1), bspecs)).compile()
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert peak <= 15.75 * 2 ** 30
    assert abs(plan.meta["step_bytes"] / peak - 1) < 0.10, (
        plan.meta["step_bytes"], peak)
