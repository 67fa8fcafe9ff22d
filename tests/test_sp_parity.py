"""SP-axis parity gate: a plan's (policy, d_s_eff) must never change the math.

The planner now chooses the SP policy and effective degree per plan
(``ExecutionPlan.sp``); the runtime realizes sub-degrees as model-axis
sub-groups with replicated chunk compute. This suite pins the semantic
contract on the remat-parity harness pattern:

* for BOTH policies (ulysses, allgather_kv) at d_s_eff in {2, 4}, the
  training loss matches the unsharded baseline (policy "none" at
  d_s_eff=1) within float32 reduction-order noise and ``n_valid`` is
  EXACT (the replica CE mask counts every token exactly once);
* gradients agree to the repo's grad-parity standard (rtol=1e-6 /
  atol=1e-7);
* the contract composes with stage-aware remat tables and holds across
  schedule backends (gpipe-1f1b and the B/W-split zero-bubble-h1);
* prefill mode refuses sub-degree plans (the token-sharded greedy fold
  assumes distinct shards per device).

Runs in subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the main pytest session keeps seeing one CPU device (see conftest.py).
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
    from repro.models import DecoderLM
    from repro.runtime import TrainStepBuilder, make_geometry
    from repro.runtime.pipeline import pipeline_loss_fn
    from repro.runtime.sharding import shard_dim_tree
    from repro.runtime.train_step import prepare_params

    def sp_case(sp_policy=None, sp_degree=0, schedule="gpipe-1f1b",
                v_stages=1, l_ckpt=0, ckpt_table=None, mode="train"):
        # n_heads=8 => n_kv_heads=4 after reduction: ulysses legal at 2 AND 4
        cfg = get_arch("llama3.2-3b").reduced(n_layers=4, d_model=64,
                                              n_heads=8, head_dim=16,
                                              vocab=256)
        mesh = make_mesh((2, 4), ("data", "model"))
        n, cap = 4, 32
        rng = np.random.default_rng(0)
        seg = np.repeat(np.arange(n, dtype=np.int32)[:, None], cap, 1)
        seg[:, -3:] = -1  # ragged tail: padding the CE mask must skip
        batch = {
            "tokens": rng.integers(0, 256, (n, cap)).astype(np.int32),
            "targets": rng.integers(0, 256, (n, cap)).astype(np.int32),
            "seg": seg,
            "pos": np.tile(np.arange(cap, dtype=np.int32), (n, 1)),
            "ctx_len": np.zeros((n,), np.int32),
        }
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        geom = make_geometry(cfg, mesh, n_chunks=n, cap=cap, ctx_cap=2 * cap,
                             l_ckpt=l_ckpt, compute_dtype=jnp.float32,
                             schedule=schedule, v_stages=v_stages,
                             ckpt_table=ckpt_table,
                             sp_policy=sp_policy, sp_degree=sp_degree)
        builder = TrainStepBuilder(cfg, mesh, geom, param_dtype=jnp.float32)
        raw = DecoderLM(cfg).init(jax.random.PRNGKey(7), jnp.float32)
        params = prepare_params(cfg, raw, mesh, jnp.float32,
                                v_stages=v_stages)
        pspecs, _, bspecs = builder.specs(jax.eval_shape(lambda: params))
        sd = shard_dim_tree(params["stages"], 4)
        loss = pipeline_loss_fn(cfg, geom, sd, pod_axis=None, mode=mode)
        fn = jax.jit(jax.shard_map(
            loss, mesh=mesh, in_specs=(pspecs, bspecs),
            out_specs=(P(), P()), check_vma=False))
        return fn, params, batch

    def loss_and_grads(fn, params, batch):
        def scalar(p):
            l, n = fn(p, batch)
            return l / n
        l, nv = fn(params, batch)
        g = jax.grad(scalar)(params)
        return (np.asarray(l), float(nv),
                [np.asarray(x) for x in jax.tree.leaves(g)])

    def check_sp_parity(results, tag, base="none@1"):
        l0, n0, g0 = results[base]
        for name, (l, n, g) in results.items():
            assert n == n0, (tag, name, n, n0)
            np.testing.assert_allclose(
                l, l0, rtol=1e-6, atol=0,
                err_msg=f"{tag}/{name}: loss drifted across SP points")
            for a, b in zip(g, g0):
                np.testing.assert_allclose(
                    a, b, rtol=1e-6, atol=1e-7,
                    err_msg=f"{tag}/{name}: grads drifted across SP points")
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
# both policies x sub-degrees vs the unsharded baseline, two schedules
# ---------------------------------------------------------------------------

SP_POINTS = [("none", 1), ("ulysses", 2), ("ulysses", 4),
             ("allgather_kv", 2), ("allgather_kv", 4)]


def test_sp_parity_gpipe():
    _run("""
        results = {}
        for policy, d in [("none", 1), ("ulysses", 2), ("ulysses", 4),
                          ("allgather_kv", 2), ("allgather_kv", 4)]:
            fn, params, batch = sp_case(sp_policy=policy, sp_degree=d)
            results[f"{policy}@{d}"] = loss_and_grads(fn, params, batch)
        check_sp_parity(results, "sp/gpipe-1f1b")
        print("OK sp parity gpipe", float(results["none@1"][0]))
    """)


def test_sp_parity_zero_bubble():
    _run("""
        results = {}
        for policy, d in [("none", 1), ("ulysses", 4),
                          ("allgather_kv", 2)]:
            fn, params, batch = sp_case(sp_policy=policy, sp_degree=d,
                                        schedule="zero-bubble-h1")
            results[f"{policy}@{d}"] = loss_and_grads(fn, params, batch)
        check_sp_parity(results, "sp/zero-bubble-h1")
        print("OK sp parity zero-bubble", float(results["none@1"][0]))
    """)


def test_sp_parity_composed_with_stage_aware_remat():
    _run("""
        TAB = ((2, 0, 1, 2), (1, 2, 0, 0))
        results = {}
        for policy, d in [("none", 1), ("ulysses", 4),
                          ("allgather_kv", 2)]:
            fn, params, batch = sp_case(sp_policy=policy, sp_degree=d,
                                        l_ckpt=2, ckpt_table=TAB)
            results[f"{policy}@{d}"] = loss_and_grads(fn, params, batch)
        check_sp_parity(results, "sp/remat-vector")
        print("OK sp parity with stage-aware remat")
    """)


def test_sp_full_degree_default_unchanged():
    """make_geometry with no SP args (the legacy call) must equal an
    explicit full-degree pin — old callers keep bitwise-identical plans."""
    _run("""
        fa, pa, ba = sp_case()                      # legacy default
        fb, pb, bb = sp_case(sp_policy="ulysses", sp_degree=4)
        la, na, ga = loss_and_grads(fa, pa, ba)
        lb, nb, gb = loss_and_grads(fb, pb, bb)
        assert na == nb
        assert la.tobytes() == lb.tobytes(), (float(la), float(lb))
        for a, b in zip(ga, gb):
            assert a.tobytes() == b.tobytes(), \\
                "explicit full-degree pin drifted from the legacy default"
        print("OK legacy default == full-degree pin", float(la))
    """)


# ---------------------------------------------------------------------------
# guard rails (no compile needed)
# ---------------------------------------------------------------------------

def test_prefill_rejects_sub_degree():
    _run("""
        fn, params, batch = None, None, None
        try:
            sp_case(sp_policy="allgather_kv", sp_degree=2, mode="prefill")
        except ValueError as e:
            assert "d_s_eff == d_s" in str(e), e
            print("OK prefill rejects sub-degree")
        else:
            raise AssertionError("prefill accepted d_s_eff < d_s")
    """)


def test_geometry_validation():
    from repro.runtime.pipeline import PipelineGeometry

    common = dict(n_chunks=2, cap=32, ctx_cap=64, d_p=2, d_s=4, l_ckpt=0,
                  layers_per_stage=2)
    with pytest.raises(ValueError, match="divide"):
        PipelineGeometry(policy="allgather_kv", d_s_eff=3, **common)
    with pytest.raises(ValueError, match="ulysses"):
        PipelineGeometry(policy="ulysses", d_s_eff=1, **common)
    g = PipelineGeometry(policy="allgather_kv", d_s_eff=2, **common)
    assert g.sp_rep == 2
    # legacy default: d_s_eff=0 resolves to the full axis
    g2 = PipelineGeometry(policy="ulysses", **common)
    assert g2.d_s_eff == 4 and g2.sp_rep == 1
