"""Planner + grouping integration tests."""

import math

import numpy as np
import pytest

from repro.core import (ClusterSpec, CostModel, ExecutionPlan, ModelSpec,
                        PlannerConfig, group_sequences, chunk_sequences,
                        plan_batch)


def test_plan_covers_all_tokens(cost_model, skewed_lengths):
    plan = plan_batch(cost_model, skewed_lengths)
    assert plan.total_tokens == sum(skewed_lengths)
    assert plan.n_chunks > 0
    assert plan.k_split >= 1
    assert plan.chunk_capacity >= max(c.tokens for p in plan.pipelines
                                      for c in p.chunks)
    assert plan.est_total_time > 0
    assert plan.solve_time > 0


def test_plan_schedules_filled(cost_model, skewed_lengths):
    plan = plan_batch(cost_model, skewed_lengths)
    for p in plan.pipelines:
        assert len(p.schedule) == cost_model.cluster.d_p
        assert len(p.ckpt) == cost_model.cluster.d_p
        assert all(len(row) == 2 * p.n_chunks for row in p.schedule)


def test_fixed_k_pins_split(cost_model, skewed_lengths):
    plan = plan_batch(cost_model, skewed_lengths, PlannerConfig(fixed_k=3))
    assert plan.k_split == 3


def test_ablations_run(cost_model, skewed_lengths):
    base = plan_batch(cost_model, skewed_lengths, PlannerConfig(fixed_k=4))
    nock = plan_batch(cost_model, skewed_lengths,
                      PlannerConfig(fixed_k=4, disable_ckpt=True))
    full = plan_batch(cost_model, skewed_lengths,
                      PlannerConfig(fixed_k=4, full_ckpt=True))
    wowbc = plan_batch(cost_model, skewed_lengths,
                       PlannerConfig(fixed_k=4, uniform_split=True))
    assert full.est_total_time >= base.est_total_time - 1e-9
    for p in nock.pipelines:
        assert all(v == 0 for row in p.ckpt for v in row)
    per_stage = cost_model.model.n_layers // cost_model.cluster.d_p
    for p in full.pipelines:
        assert all(v == per_stage for row in p.ckpt for v in row)
    assert wowbc.total_tokens == sum(skewed_lengths)


def test_grouping_splits_under_memory_pressure():
    """One gigantic sequence + many shorts with tight memory should produce
    more than one 1F1B pipeline (Fig. 5b) OR heavy checkpointing."""
    m = ModelSpec(name="t", n_layers=16, d_model=2048, n_heads=16,
                  n_kv_heads=8, head_dim=128, d_ff=8192, vocab=64000)
    cm = CostModel(m, ClusterSpec(d_p=4, d_s=4, hbm_bytes=10e9))
    lengths = [262144] + [2048] * 60
    plan = plan_batch(cm, lengths)
    assert plan.total_tokens == sum(lengths)
    ckpt_layers = sum(sum(row) for p in plan.pipelines for row in p.ckpt)
    assert len(plan.pipelines) >= 2 or ckpt_layers > 0


def test_plan_serialization_roundtrip(cost_model, skewed_lengths):
    plan = plan_batch(cost_model, skewed_lengths, PlannerConfig(fixed_k=2))
    blob = plan.dumps()
    back = ExecutionPlan.loads(blob)
    assert back.k_split == plan.k_split
    assert back.n_chunks == plan.n_chunks
    assert back.total_tokens == plan.total_tokens
    assert [c.tokens for p in back.pipelines for c in p.chunks] == \
           [c.tokens for p in plan.pipelines for c in p.chunks]
    assert back.pipelines[0].schedule[0][0].op == \
           plan.pipelines[0].schedule[0][0].op


def test_straggler_replanning_rebalances():
    """With a slowed stage, the planner's estimate grows but stays feasible —
    the ft layer uses this loop for straggler mitigation."""
    m = ModelSpec(name="t", n_layers=8, d_model=512, n_heads=8, n_kv_heads=4,
                  head_dim=64, d_ff=2048, vocab=8192)
    cm = CostModel(m, ClusterSpec(d_p=4, d_s=4))
    lengths = [16384] + [1024] * 24
    base = plan_batch(cm, lengths, PlannerConfig(fixed_k=3))
    slow = plan_batch(cm.with_slowdowns([1.0, 1.0, 1.8, 1.0]), lengths,
                      PlannerConfig(fixed_k=3))
    assert slow.est_total_time > base.est_total_time


def test_plan_fits_the_compiled_step():
    """The planner raises remat until the executor's step fits: the ILP's
    1F1B windows admit no recomputation for llama3.2-3b on one v5e, but
    the compiled step keeps the whole 9216-token chunk's residuals, so
    one of the two layers is recomputed — a uniform depth the digest
    records (the v5e compiler's peak of this one-chunk step is 15.29e9 B
    at depth 0 and 11.78e9 B at depth 1)."""
    import dataclasses

    from repro.configs import get_arch
    spec = dataclasses.replace(get_arch("llama3.2-3b").spec, n_layers=2)
    cm = CostModel(spec, ClusterSpec.for_device("tpu", "TPU v5 lite",
                                                d_p=1, d_s=1))
    plan = plan_batch(cm, [9176], PlannerConfig(bucket_rounding=256,
                                                remat_mode="stage_aware"))
    key = plan.bucket_key(1)
    assert key.n_chunks == 1
    assert key.ckpt == "u1" and key.l_ckpt == 1
    assert plan.meta["step_bytes"] <= cm.cluster.capacity_bytes
    assert cm.m_step(n_ticks=1, cap=key.cap, ctx_cap=key.ctx_cap,
                     l_ckpt=0) > cm.cluster.capacity_bytes


def test_ckpt_floor_forces_uniform_depth(cost_model, skewed_lengths):
    """A remat floor (what the trainer sets when the compiler refuses a
    step) lifts every (stage, chunk) to at least that depth."""
    base = plan_batch(cost_model, skewed_lengths,
                      PlannerConfig(fixed_k=4, remat_mode="stage_aware"))
    assert min(v for row in base.ckpt_table() for v in row) < 2
    plan = plan_batch(cost_model, skewed_lengths,
                      PlannerConfig(fixed_k=4, ckpt_floor=2,
                                    remat_mode="stage_aware"))
    assert {v for row in plan.ckpt_table() for v in row} == {2}
    assert plan.bucket_key(4).ckpt == "u2"
