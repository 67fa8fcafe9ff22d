"""Bucket chunk-count rounding (``ExecutionPlan.bucket_key``).

A bucket's chunk count rounds up to a power of two while the plan has at
most ``chunk_rounding`` real chunks, and to a multiple of
``chunk_rounding`` above it. These tests pin the values, the bounds
against the plain multiple, that plans of ``chunk_rounding`` or more
chunks keep the plain multiple's whole key, that the planner prices the
smaller bucket (a one-chunk step needs no recomputation at Qwen3-4B
widths on a v5e), and that the padding chunks the old rule added carried
no loss and no gradient.
"""

import numpy as np
import pytest

from repro.core import (ClusterSpec, CostModel, ModelSpec, PlannerConfig,
                        plan_batch)
from repro.core import plan as plan_mod
from repro.core.plan import (Chunk, ChunkKind, ExecutionPlan, PipelinePlan,
                             Slice, round_chunk_count)


def _multiple(n: int, chunk_rounding: int = 8) -> int:
    """The rule every count used before: the next multiple."""
    return -(-n // chunk_rounding) * chunk_rounding


def _plan(n: int, d_p: int = 2, vector: bool = False) -> ExecutionPlan:
    """A plan of ``n`` real one-sequence chunks on ``d_p`` stages; with
    ``vector`` its stage-aware remat table differs across chunks."""
    chunks = [Chunk(kind=ChunkKind.BATCHED, context=0,
                    slices=(Slice(seq_id=i, start=0, length=256,
                                  is_tail=True),))
              for i in range(n)]
    ckpt = [[(k + r) % 3 if vector else 1 for k in range(n)]
            for r in range(d_p)]
    pipe = PipelinePlan(chunks=chunks, f2b=list(range(n)), ckpt=ckpt)
    return ExecutionPlan(pipelines=[pipe], sequences=[], k_split=1,
                         chunk_capacity=256, mesh_slices=[256],
                         remat_mode="stage_aware")


EXPECTED = [1, 2, 4, 4, 8, 8, 8, 8] + [16] * 8 + [24] * 4


@pytest.mark.parametrize("n,want", list(enumerate(EXPECTED, start=1)))
def test_bucket_key_n_chunks(n, want):
    assert round_chunk_count(n) == want
    assert _plan(n).bucket_key(4).n_chunks == want


@pytest.mark.parametrize("chunk_rounding", [1, 2, 3, 4, 6, 8, 16])
def test_rounding_bounds(chunk_rounding):
    """Never fewer than the real chunks, never more than the plain
    multiple, never more than twice the real chunks."""
    assert round_chunk_count(0, chunk_rounding) == 0
    for n in range(1, 80):
        got = round_chunk_count(n, chunk_rounding)
        assert n <= got <= _multiple(n, chunk_rounding), (n, got)
        assert got <= 2 * n, (n, got)
        if n >= chunk_rounding:
            assert got == _multiple(n, chunk_rounding), (n, got)


def _parent_key(plan: ExecutionPlan, monkeypatch, d_s: int = 4, **kw):
    """``plan``'s key under the plain-multiple rule."""
    with monkeypatch.context() as m:
        m.setattr(plan_mod, "round_chunk_count", _multiple)
        return plan.bucket_key(d_s, **kw)


@pytest.mark.parametrize("vector", [False, True])
def test_large_plans_keep_their_keys(vector, monkeypatch):
    """A plan of 8 or more real chunks keys exactly as before: same
    count, same remat digest over the padded table, same everything."""
    for n in range(8, 41):
        for d_p in (1, 2, 4):
            plan = _plan(n, d_p=d_p, vector=vector)
            for kw in ({}, {"split_bwd": "on", "dtype": "float32"}):
                assert plan.bucket_key(4, **kw) == \
                    _parent_key(plan, monkeypatch, **kw), (n, d_p, kw)


def test_planned_large_plans_keep_their_keys(monkeypatch):
    """The same for the planner's own plans of 8 or more chunks."""
    m = ModelSpec(name="t", n_layers=8, d_model=256, n_heads=8,
                  n_kv_heads=4, head_dim=32, d_ff=1024, vocab=512)
    cm = CostModel(m, ClusterSpec(d_p=4, d_s=4))
    seen = 0
    for mix in ([4096] * 3, [8192, 2048, 512, 300, 200], [16384] * 2,
                [32768]):
        for remat in ("uniform", "stage_aware"):
            plan = plan_batch(cm, mix, PlannerConfig(bucket_rounding=64,
                                                     remat_mode=remat))
            assert plan.n_chunks >= 8, mix
            assert plan.bucket_key(4) == _parent_key(plan, monkeypatch)
            seen += 1
    assert seen == 8


QWEN3_4B_2L = ModelSpec(name="qwen3-d2560-l2", n_layers=2, d_model=2560,
                        n_heads=32, n_kv_heads=8, head_dim=128, d_ff=9728,
                        vocab=151936, qk_norm=True, tie_embeddings=True)


def test_github_1k_batches_plan_one_chunk_without_remat():
    """The 1024-token github batches at Qwen3-4B widths (2 layers) on one
    v5e, planned as the training loop plans them: each is one chunk in a
    one-chunk bucket, and the planner, pricing that bucket, keeps every
    residual (no recomputation)."""
    from repro.core.planner import step_memory
    cm = CostModel(QWEN3_4B_2L, ClusterSpec.for_device(
        "tpu", "TPU v5 lite", d_p=1, d_s=1))
    schedule, v_stages = None, 0
    for lengths in ([1024], [1024], [969, 55], [772, 252]):
        plan = plan_batch(cm, lengths, PlannerConfig(
            bucket_rounding=256, schedule=schedule, v_stages=v_stages,
            remat_mode="stage_aware", ckpt_floor=0, sp_policy="auto",
            sp_degree=0))
        schedule, v_stages = plan.schedule, plan.v_stages
        key = plan.bucket_key(1, split_bwd="auto", dtype="bfloat16")
        assert plan.n_chunks == 1 and key.n_chunks == 1, (lengths, key)
        assert key.ckpt == "u0" and key.l_ckpt == 0, (lengths, key)
        assert step_memory(cm, plan) <= cm.cluster.capacity_bytes


# ---------------------------------------------------------------------------
# The padding chunks carried nothing: one batch through a one-chunk
# geometry and through an eight-chunk geometry (seven fully masked
# chunks) gives the same loss and gradients, at d_p = 1.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("schedule,v_stages", [("gpipe-1f1b", 1),
                                               ("zero-bubble-h1", 1),
                                               ("interleaved-1f1b", 2)])
def test_one_chunk_matches_eight_chunk_bucket(schedule, v_stages):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_arch
    from repro.launch.mesh import make_mesh
    from repro.models import DecoderLM
    from repro.runtime import TrainStepBuilder, make_geometry
    from repro.runtime.pipeline import pipeline_loss_fn
    from repro.runtime.sharding import shard_dim_tree
    from repro.runtime.train_step import prepare_params

    cfg = get_arch("llama3.2-3b").reduced(n_layers=4, d_model=64, n_heads=4,
                                          head_dim=16, vocab=256)
    mesh = make_mesh((1, 1), ("data", "model"))
    cap = 32
    rng = np.random.default_rng(0)
    real = {"tokens": rng.integers(0, 256, (1, cap)).astype(np.int32),
            "targets": rng.integers(0, 256, (1, cap)).astype(np.int32),
            # two packed documents in the one real chunk
            "seg": np.repeat(np.array([[0, 1]], np.int32), cap // 2, 1),
            "pos": np.tile(np.arange(cap // 2, dtype=np.int32), (1, 2)),
            "ctx_len": np.zeros((1,), np.int32)}
    fill = {"tokens": 0, "targets": -1, "seg": -1, "pos": 0, "ctx_len": 0}
    raw = DecoderLM(cfg).init(jax.random.PRNGKey(7), jnp.float32)

    def run(n):
        batch = {}
        for k, a in real.items():
            out = np.full((n, *a.shape[1:]), fill[k], a.dtype)
            out[:1] = a
            batch[k] = jnp.asarray(out)
        geom = make_geometry(cfg, mesh, n_chunks=n, cap=cap, ctx_cap=2 * cap,
                             l_ckpt=0, compute_dtype=jnp.float32,
                             schedule=schedule, v_stages=v_stages)
        builder = TrainStepBuilder(cfg, mesh, geom, param_dtype=jnp.float32)
        params = prepare_params(cfg, raw, mesh, jnp.float32,
                                v_stages=v_stages)
        pspecs, _, bspecs = builder.specs(jax.eval_shape(lambda: params))
        sd = shard_dim_tree(params["stages"], 1)
        fn = jax.jit(jax.shard_map(
            pipeline_loss_fn(cfg, geom, sd, pod_axis=None), mesh=mesh,
            in_specs=(pspecs, bspecs), out_specs=(P(), P()),
            check_vma=False))

        def scalar(p):
            loss, n_valid = fn(p, batch)
            return loss / n_valid
        loss, n_valid = fn(params, batch)
        grads = jax.grad(scalar)(params)
        return (np.asarray(loss), float(n_valid),
                [np.asarray(g) for g in jax.tree.leaves(grads)])

    (l1, n1, g1), (l8, n8, g8) = run(1), run(8)
    assert n1 == n8 == cap
    np.testing.assert_allclose(l1, l8, rtol=1e-6)
    assert len(g1) == len(g8)
    for a, b in zip(g1, g8):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
