"""Distributed-runtime equivalence tests.

These run in SUBPROCESSES with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the main pytest session keeps seeing exactly one CPU device (the dry-run
flag must never leak — see conftest.py). Each scenario script builds a tiny
arch on a (data=2, model=4) mesh, runs the shard_map'd EPP pipeline loss,
and compares against the single-device reference model on the same chunks.
"""

import os
import subprocess
import sys
import textwrap

import pytest

_COMMON = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from jax.sharding import PartitionSpec as P
    jax.config.update("jax_default_matmul_precision", "highest")

    from repro.configs import get_arch
    from repro.core import ClusterSpec, CostModel, PlannerConfig, plan_batch
    from repro.data import materialize_plan, sample_corpus_batch
    from repro.models import DecoderLM, LayerCtx
    from repro.runtime import TrainStepBuilder, make_geometry
    from repro.runtime.pipeline import pipeline_loss_fn
    from repro.runtime.sharding import shard_dim_tree, mesh_axis_names
    from repro.runtime.train_step import prepare_params, param_pspecs, batch_specs, batch_struct

    def reference_loss(cfg, raw_params, chunks, corpus, cap, ctx_cap):
        model = DecoderLM(cfg)
        total, count = jnp.float32(0), jnp.float32(0)
        from repro.data.batching import materialize_chunks
        cb = materialize_chunks(chunks, corpus, cap)
        ctx = model.init_ctx(ctx_cap, jnp.float32)
        for k in range(cb.tokens.shape[0]):
            tok = jnp.asarray(cb.tokens[k]); tgt = jnp.asarray(cb.targets[k])
            sg = jnp.asarray(cb.seg[k]); ps = jnp.asarray(cb.pos[k])
            cl = int(cb.ctx_len[k])
            if cl == 0 and ctx.ssm_h is not None:
                ctx = ctx._replace(ssm_h=jnp.zeros_like(ctx.ssm_h))
            h, ctx = model.forward_chunk(raw_params, tok, sg, ps, ctx=ctx,
                                         ctx_len=cl, compute_dtype=jnp.float32)
            s, n = model.chunk_loss(raw_params, h, tgt, sg)
            total += s; count += n
        return total, count

    def run_case(arch, seed=0, n_seq=6, ctx_limit=192, fixed_k=2):
        cfg = get_arch(arch).reduced(n_layers=4, d_model=64, n_heads=4,
                                     head_dim=16, vocab=256)
        mesh = make_mesh((2, 4), ("data", "model"))
        cm = CostModel(cfg.spec, ClusterSpec(d_p=2, d_s=4))
        rng = np.random.default_rng(seed)
        lens = [ctx_limit] + [int(x) for x in rng.integers(24, ctx_limit // 2, n_seq - 1)]
        corpus = {i: rng.integers(0, cfg.spec.vocab, l).astype(np.int32)
                  for i, l in enumerate(lens)}
        plan = plan_batch(cm, lens, PlannerConfig(fixed_k=fixed_k,
                                                  bucket_rounding=16))
        batch_np = materialize_plan(plan, corpus)
        chunks = [c for p in plan.pipelines for c in p.chunks]
        cap = plan.chunk_capacity
        # pad cap to a multiple of d_s for token sharding
        d_s = 4
        cap_pad = ((cap + d_s - 1)//d_s)*d_s
        import numpy as _np
        def padcap(a):
            if a.ndim == 2 and a.shape[1] == cap:
                out = _np.full((a.shape[0], cap_pad), -1 if a.dtype == _np.int32 else 0, a.dtype)
                out[:, :cap] = a
                if a is batch_np.tokens or a is batch_np.pos: out[:, cap:] = 0
                return out
            return a
        batch = {
            "tokens": _np.where(batch_np.seg >= 0, batch_np.tokens, 0),
            "targets": batch_np.targets, "seg": batch_np.seg,
            "pos": _np.where(batch_np.seg >= 0, batch_np.pos, 0),
            "ctx_len": batch_np.ctx_len}
        def pad2(a, fill):
            out = _np.full((a.shape[0], cap_pad), fill, a.dtype)
            out[:, :a.shape[1]] = a
            return out
        batch = {
            "tokens": pad2(batch["tokens"], 0),
            "targets": pad2(batch["targets"], -1),
            "seg": pad2(batch["seg"], -1),
            "pos": pad2(batch["pos"], 0),
            "ctx_len": batch["ctx_len"]}
        batch = {k: jnp.asarray(v) for k, v in batch.items()}

        ctx_cap = ctx_limit + cap_pad  # appends write cap rows at offset C_k
        geom = make_geometry(cfg, mesh, n_chunks=len(chunks), cap=cap_pad,
                             ctx_cap=ctx_cap, l_ckpt=0,
                             compute_dtype=jnp.float32)
        builder = TrainStepBuilder(cfg, mesh, geom, param_dtype=jnp.float32)
        model = DecoderLM(cfg)
        raw = model.init(jax.random.PRNGKey(7), jnp.float32)
        params = prepare_params(cfg, raw, mesh, jnp.float32)
        pspecs, _, bspecs = builder.specs(jax.eval_shape(lambda: params))
        shard_dims = shard_dim_tree(params["stages"], 4)

        loss_fn = pipeline_loss_fn(cfg, geom, shard_dims, pod_axis=None)
        mapped = jax.jit(jax.shard_map(
            loss_fn, mesh=mesh, in_specs=(pspecs, bspecs),
            out_specs=(P(), P()), check_vma=False))
        loss_d, n_d = mapped(params, batch)

        loss_r, n_r = reference_loss(cfg, raw, chunks, corpus, cap_pad,
                                     ctx_cap)
        print("dist:", float(loss_d), float(n_d), " ref:", float(loss_r), float(n_r))
        assert int(n_d) == int(n_r), (n_d, n_r)
        rel = abs(float(loss_d) - float(loss_r)) / max(abs(float(loss_r)), 1e-9)
        assert rel < 2e-4, f"loss mismatch rel={rel}"
        print("OK", arch)
""")


def _run(case: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _COMMON + case],
                       capture_output=True, text=True, env=env, timeout=900)
    if r.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}")
    assert "OK" in r.stdout


@pytest.mark.parametrize("arch", ["llama3.2-3b",        # allgather_kv GQA
                                  "qwen3-4b",           # ulysses-capable
                                  "gemma3-1b",          # local:global, MQA
                                  "olmoe-1b-7b",        # MoE EP
                                  "deepseek-v2-lite",   # MLA + MoE
                                  "falcon-mamba-7b",    # SSM SP scan
                                  "hymba-1.5b"])        # hybrid
def test_pipeline_matches_reference(arch):
    _run(f"\nrun_case({arch!r})\n")


def test_pipeline_with_remat_matches():
    """l_ckpt > 0 must not change the loss (only the memory profile)."""
    _run(textwrap.dedent("""
        cfg = get_arch("llama3.2-3b").reduced(n_layers=4, d_model=64,
                                              n_heads=4, head_dim=16,
                                              vocab=256)
        mesh = make_mesh((2, 4), ("data", "model"))
        rng = np.random.default_rng(3)
        lens = [160, 40, 30]
        corpus = {i: rng.integers(0, 256, l).astype(np.int32)
                  for i, l in enumerate(lens)}
        cm = CostModel(cfg.spec, ClusterSpec(d_p=2, d_s=4))
        plan = plan_batch(cm, lens, PlannerConfig(fixed_k=2, bucket_rounding=16))
        from repro.data import materialize_plan
        batch_np = materialize_plan(plan, corpus)
        cap = plan.chunk_capacity
        batch = {k: jnp.asarray(v) for k, v in batch_np.as_dict().items()}
        batch["tokens"] = jnp.where(batch["seg"] >= 0, batch["tokens"], 0)

        model = DecoderLM(cfg)
        raw = model.init(jax.random.PRNGKey(1), jnp.float32)
        params = prepare_params(cfg, raw, mesh, jnp.float32)
        shard_dims = shard_dim_tree(params["stages"], 4)
        losses = []
        n_chunks = sum(len(p.chunks) for p in plan.pipelines)
        for l_ckpt in (0, 1, 2):
            geom = make_geometry(cfg, mesh, n_chunks=n_chunks, cap=cap,
                                 ctx_cap=200, l_ckpt=l_ckpt,
                                 compute_dtype=jnp.float32)
            builder = TrainStepBuilder(cfg, mesh, geom, param_dtype=jnp.float32)
            pspecs, _, bspecs = builder.specs(jax.eval_shape(lambda: params))
            loss_fn = pipeline_loss_fn(cfg, geom, shard_dims, pod_axis=None)
            mapped = jax.jit(jax.shard_map(
                loss_fn, mesh=mesh, in_specs=(pspecs, bspecs),
                out_specs=(P(), P()), check_vma=False))
            # also check gradients flow under remat
            def scalar(p):
                l, n = mapped(p, batch)
                return l / n
            val, grads = jax.value_and_grad(scalar)(params)
            losses.append(float(val))
            gleaves = jax.tree.leaves(grads)
            assert all(np.all(np.isfinite(np.asarray(g))) for g in gleaves)
        assert abs(losses[0] - losses[1]) < 1e-5
        assert abs(losses[0] - losses[2]) < 1e-5
        print("OK remat", losses)
    """))
