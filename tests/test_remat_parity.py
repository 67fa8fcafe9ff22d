"""Remat/schedule parity harness: recomputation must never change the math.

The stage-aware adaptive-checkpointing refactor threads per-(stage, chunk)
``l_ckpt`` vectors from the ILP all the way into the compiled step
(solver -> ``ExecutionPlan.ckpt_table`` -> ``bucket_key().ckpt`` ->
``executor.remat_tick_count`` -> ``run_stage_layers``). This suite pins the
semantic contract for every pipeline backend (decoder train, enc-dec
train, serve/prefill) under every schedule backend (``gpipe-1f1b``,
``interleaved-1f1b`` at the highest supported v, ``zero-bubble-h1``):

* **losses / prefill ids are bitwise identical** across remat policies
  ``l_ckpt = 0``, the uniform max, and a non-uniform per-(stage, chunk)
  vector — remat choices may only move memory, never a single output bit;
* **gradients agree to the repo's grad-parity standard** (allclose at
  rtol=1e-6 / atol=1e-7 — the same bound the executor-core refactor tests
  use). They are NOT asserted bitwise across *different* remat depths:
  ``jax.checkpoint`` itself reorders backward fusion, so even the two
  pre-existing static splits (l=0 vs l=2) differ in final-ULP noise;
* at **equal depth** the static split path (uniform int) and the traced
  per-tick path (constant table) ARE bitwise identical — loss AND grads —
  which locks the new dynamic ``lax.cond`` remat machinery against drift.

Runs in subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the main pytest session keeps seeing one CPU device (see conftest.py).
"""

import os
import subprocess
import sys
import textwrap

_COMMON = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from jax.sharding import PartitionSpec as P
    jax.config.update("jax_default_matmul_precision", "highest")

    from repro.configs import get_arch
    from repro.models import DecoderLM, EncDecLM
    from repro.runtime import TrainStepBuilder, make_geometry
    from repro.runtime.pipeline import pipeline_loss_fn
    from repro.runtime.sharding import (batch_specs, shard_dim_tree, stage_param_specs)
    from repro.runtime.train_step import prepare_params

    SCHEDULES = [("gpipe-1f1b", 1), ("interleaved-1f1b", 2),
                 ("zero-bubble-h1", 1)]

    def decoder_case(l_ckpt=0, ckpt_table=None, schedule="gpipe-1f1b",
                     v_stages=1, mode="train"):
        cfg = get_arch("llama3.2-3b").reduced(n_layers=4, d_model=64,
                                              n_heads=4, head_dim=16,
                                              vocab=256)
        mesh = make_mesh((2, 4), ("data", "model"))
        n, cap = 4, 32
        rng = np.random.default_rng(0)
        batch = {
            "tokens": rng.integers(0, 256, (n, cap)).astype(np.int32),
            "targets": rng.integers(0, 256, (n, cap)).astype(np.int32),
            "seg": np.repeat(np.arange(n, dtype=np.int32)[:, None], cap, 1),
            "pos": np.tile(np.arange(cap, dtype=np.int32), (n, 1)),
            "ctx_len": np.zeros((n,), np.int32),
        }
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        geom = make_geometry(cfg, mesh, n_chunks=n, cap=cap, ctx_cap=2 * cap,
                             l_ckpt=l_ckpt, compute_dtype=jnp.float32,
                             schedule=schedule, v_stages=v_stages,
                             ckpt_table=ckpt_table)
        builder = TrainStepBuilder(cfg, mesh, geom, param_dtype=jnp.float32)
        raw = DecoderLM(cfg).init(jax.random.PRNGKey(7), jnp.float32)
        params = prepare_params(cfg, raw, mesh, jnp.float32,
                                v_stages=v_stages)
        pspecs, _, bspecs = builder.specs(jax.eval_shape(lambda: params))
        sd = shard_dim_tree(params["stages"], 4)
        loss = pipeline_loss_fn(cfg, geom, sd, pod_axis=None, mode=mode)
        if mode == "prefill":
            def ids_only(p, b):
                ids, _ctx = loss(p, b)
                return ids
            fn = jax.jit(jax.shard_map(
                ids_only, mesh=mesh, in_specs=(pspecs, bspecs),
                out_specs=P(None, "model"), check_vma=False))
        else:
            fn = jax.jit(jax.shard_map(
                loss, mesh=mesh, in_specs=(pspecs, bspecs),
                out_specs=(P(), P()), check_vma=False))
        return fn, params, batch

    def encdec_case(l_ckpt=0, ckpt_table=None, schedule="gpipe-1f1b"):
        from repro.runtime.encdec_pipeline import (
            encdec_batch_struct, encdec_pipeline_loss_fn,
            make_encdec_geometry, prepare_encdec_params)
        cfg = get_arch("seamless-m4t-v2").reduced(n_layers=2, d_model=64,
                                                  n_heads=4, head_dim=16,
                                                  vocab=256)
        mesh = make_mesh((2, 4), ("data", "model"))
        n, cap = 3, 32
        geom = make_encdec_geometry(cfg, mesh, n_chunks=n, cap=cap,
                                    cap_enc=cap, ctx_cap=2 * cap,
                                    l_ckpt=l_ckpt, ckpt_table=ckpt_table,
                                    compute_dtype=jnp.float32,
                                    schedule=schedule)
        raw = EncDecLM(cfg).init(jax.random.PRNGKey(5), jnp.float32)
        params = prepare_encdec_params(cfg, raw, geom, jnp.float32)
        pspecs = {
            "stages": stage_param_specs(
                jax.eval_shape(lambda: params)["stages"], 4, pod=None),
            "embed": P("model", None),
            "enc_norm": P("model"),
            "final_norm": P("model"),
        }
        sd = shard_dim_tree(params["stages"], 4)
        bstruct = encdec_batch_struct(geom, cfg, 1)
        bspecs = batch_specs(bstruct, pod=None, model="model")
        rng = np.random.default_rng(2)
        batch = {}
        for k, v in bstruct.items():
            if v.dtype == jnp.int32:
                if k.startswith("seg") or k == "ctx_len":
                    arr = np.zeros(v.shape, np.int32)
                elif k.startswith("pos"):
                    arr = np.tile(np.arange(v.shape[-1], dtype=np.int32),
                                  (*v.shape[:-1], 1))
                else:
                    arr = rng.integers(0, 256, v.shape).astype(np.int32)
            else:
                arr = rng.normal(0, 0.5, v.shape).astype(np.float32)
            batch[k] = jnp.asarray(arr)
        fn = jax.jit(jax.shard_map(
            encdec_pipeline_loss_fn(cfg, geom, sd, pod_axis=None),
            mesh=mesh, in_specs=(pspecs, bspecs), out_specs=(P(), P()),
            check_vma=False))
        return fn, params, batch

    def loss_and_grads(fn, params, batch):
        def scalar(p):
            l, n = fn(p, batch)
            return l / n
        l, nv = fn(params, batch)
        g = jax.grad(scalar)(params)
        return (np.asarray(l), float(nv),
                [np.asarray(x) for x in jax.tree.leaves(g)])

    def check_parity(results, tag):
        # results: {policy: (loss, n_valid, grad_leaves)}
        (l0, n0, g0) = next(iter(results.values()))
        for name, (l, n, g) in results.items():
            assert n == n0, (tag, name, n, n0)
            assert l.tobytes() == l0.tobytes(), \\
                (tag, name, float(l), float(l0))
            for a, b in zip(g, g0):
                np.testing.assert_allclose(
                    a, b, rtol=1e-6, atol=1e-7,
                    err_msg=f"{tag}/{name}: grads drifted across remat")
""")


def _run(case: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _COMMON + textwrap.dedent(case)],
                       capture_output=True, text=True, env=env, timeout=900)
    if r.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}")
    assert "OK" in r.stdout


# ---------------------------------------------------------------------------
# Decoder backend: all three schedule backends x {0, uniform, vector}.
# ---------------------------------------------------------------------------

def test_decoder_remat_parity_all_schedules():
    _run("""
        # non-uniform per-(stage, chunk) table: stages AND chunks differ
        TAB = ((2, 0, 1, 2), (1, 2, 0, 0))
        for schedule, v in SCHEDULES:
            results = {}
            for policy, kw in [
                ("l0", dict(l_ckpt=0)),
                ("uniform", dict(l_ckpt=2)),
                ("vector", dict(l_ckpt=2, ckpt_table=TAB)),
            ]:
                fn, params, batch = decoder_case(
                    schedule=schedule, v_stages=v, **kw)
                results[policy] = loss_and_grads(fn, params, batch)
            check_parity(results, f"decoder/{schedule}-v{v}")
            print("parity", schedule, v,
                  float(results["vector"][0]))
        print("OK decoder remat parity")
    """)


# ---------------------------------------------------------------------------
# Enc-dec backend: encoder rows of the vector differ from decoder rows.
# ---------------------------------------------------------------------------

def test_encdec_remat_parity_all_schedules():
    _run("""
        # stage 0 is the encoder stage, stage 1 the decoder stage — give
        # them DIFFERENT depths per chunk (the ROADMAP's enc/dec split)
        TAB = ((1, 0, 2), (0, 2, 1))
        # grouped enc+dec stacking has no interleaved placement, so the
        # interleaved backend runs at v=1 (tick map == the 1F1B diagonal)
        for schedule in ("gpipe-1f1b", "interleaved-1f1b", "zero-bubble-h1"):
            results = {}
            for policy, kw in [
                ("l0", dict(l_ckpt=0)),
                ("uniform", dict(l_ckpt=2)),
                ("vector", dict(l_ckpt=2, ckpt_table=TAB)),
            ]:
                fn, params, batch = encdec_case(schedule=schedule, **kw)
                results[policy] = loss_and_grads(fn, params, batch)
            check_parity(results, f"encdec/{schedule}")
            print("parity encdec", schedule, float(results["vector"][0]))
        print("OK encdec remat parity")
    """)


def test_encdec_rejects_virtual_stages():
    """EncDecGeometry pins v_stages=1: the grouped enc+dec layer stacking
    has no interleaved placement, so requesting v>1 must be a loud error,
    never a silently wrong layout."""
    import pytest

    from repro.runtime.encdec_pipeline import EncDecGeometry
    with pytest.raises(ValueError, match="v_stages=1"):
        EncDecGeometry(n_chunks=2, cap=32, cap_enc=32, ctx_cap=64, d_p=2,
                       d_s=4, l_ckpt=0, enc_stages=1, layers_per_stage=2,
                       v_stages=2)


# ---------------------------------------------------------------------------
# Serve backend (prefill): forward-only — greedy ids bitwise across remat.
# ---------------------------------------------------------------------------

def test_serve_prefill_remat_parity_all_schedules():
    _run("""
        TAB = ((2, 0, 1, 2), (1, 2, 0, 0))
        for schedule, v in SCHEDULES:
            ids = {}
            for policy, kw in [
                ("l0", dict(l_ckpt=0)),
                ("uniform", dict(l_ckpt=2)),
                ("vector", dict(l_ckpt=2, ckpt_table=TAB)),
            ]:
                fn, params, batch = decoder_case(
                    schedule=schedule, v_stages=v, mode="prefill", **kw)
                ids[policy] = np.asarray(fn(params, batch))
            base = ids["l0"]
            for name, got in ids.items():
                np.testing.assert_array_equal(
                    got, base, err_msg=f"prefill/{schedule}/{name}")
            print("parity prefill", schedule, v)
        print("OK prefill remat parity")
    """)


# ---------------------------------------------------------------------------
# Static split == traced per-tick lookup at equal depth, BITWISE (loss AND
# grads): locks the dynamic lax.cond remat path against numerical drift.
# ---------------------------------------------------------------------------

def test_static_and_dynamic_paths_bitwise_at_equal_depth():
    _run("""
        CONST = ((2, 2, 2, 2), (2, 2, 2, 2))
        fs, ps, bs = decoder_case(l_ckpt=2)
        fd, pd, bd = decoder_case(l_ckpt=2, ckpt_table=CONST)
        ls, ns, gs = loss_and_grads(fs, ps, bs)
        ld, nd, gd = loss_and_grads(fd, pd, bd)
        assert ns == nd
        assert ls.tobytes() == ld.tobytes(), (float(ls), float(ld))
        for a, b in zip(gs, gd):
            assert a.tobytes() == b.tobytes(), \\
                "dynamic remat path drifted from the static split"
        print("OK static==dynamic bitwise", float(ld))
    """)
