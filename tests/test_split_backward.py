"""Split-backward / double-buffered hand-off parity harness.

The zero-bubble refactor gives the executor two new compiled shapes
(``runtime/executor.py``):

* **B/W backward split** (``split_backward_stage`` + the W-drain scan):
  the critical-path tick computes only activation cotangents and stashes
  the boundary residuals; dedicated drain ticks recompute the weight
  grads during cooldown — ZB-H1's W-grad fill, now present in the HLO.
* **Double-buffered hand-off** (``overlap_handoff``): the stream
  ppermute is issued before the accumulator fold so XLA's async
  collectives + latency-hiding scheduler can overlap them.

Both are pure scheduling transforms — this suite pins that they never
change the math:

* losses are **bitwise identical** between the fused autodiff transpose
  and the split path, for every schedule backend (the split is forced on
  via ``make_geometry(split_bwd=True)`` even for fused-schedule names);
* gradients agree at the repo grad-parity standard (rtol=1e-6 /
  atol=1e-7 — weight grads are *recomputed* in the drain, so fusion
  differs in final-ULP noise, same as remat);
* ``overlap_handoff`` on/off is bitwise identical, loss AND grads (the
  fold consumes the pre-permute buffer either way);
* the split composes with the traced per-(stage, chunk) remat table.

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
    from repro.models import DecoderLM
    from repro.runtime import TrainStepBuilder, make_geometry
    from repro.runtime.pipeline import pipeline_loss_fn
    from repro.runtime.sharding import shard_dim_tree
    from repro.runtime.train_step import prepare_params

    SCHEDULES = [("gpipe-1f1b", 1), ("interleaved-1f1b", 2),
                 ("zero-bubble-h1", 1)]

    def decoder_case(l_ckpt=0, ckpt_table=None, schedule="gpipe-1f1b",
                     v_stages=1, split_bwd=None, overlap_handoff=True):
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
                             ckpt_table=ckpt_table, split_bwd=split_bwd,
                             overlap_handoff=overlap_handoff)
        builder = TrainStepBuilder(cfg, mesh, geom, param_dtype=jnp.float32)
        raw = DecoderLM(cfg).init(jax.random.PRNGKey(7), jnp.float32)
        params = prepare_params(cfg, raw, mesh, jnp.float32,
                                v_stages=v_stages)
        pspecs, _, bspecs = builder.specs(jax.eval_shape(lambda: params))
        sd = shard_dim_tree(params["stages"], 4)
        loss = pipeline_loss_fn(cfg, geom, sd, pod_axis=None)
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

    def check_split_parity(fused, split, tag):
        (lf, nf, gf), (ls, ns, gs) = fused, split
        assert nf == ns, (tag, nf, ns)
        assert lf.tobytes() == ls.tobytes(), (tag, float(lf), float(ls))
        for a, b in zip(gf, gs):
            np.testing.assert_allclose(
                a, b, rtol=1e-6, atol=1e-7,
                err_msg=f"{tag}: grads drifted across the B/W split")
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
# Fused vs split across every schedule backend. split_bwd=True is forced
# even for the fused-schedule names: the split is a property of the
# executor, not of the tick map, and must be correct anywhere.
# ---------------------------------------------------------------------------

def test_split_backward_parity_all_schedules():
    _run("""
        for schedule, v in SCHEDULES:
            fused = loss_and_grads(*decoder_case(
                schedule=schedule, v_stages=v, split_bwd=False))
            split = loss_and_grads(*decoder_case(
                schedule=schedule, v_stages=v, split_bwd=True))
            check_split_parity(fused, split, f"{schedule}-v{v}")
            print("split parity", schedule, v, float(split[0]))
        print("OK split-backward parity")
    """)


# ---------------------------------------------------------------------------
# zero-bubble-h1's default geometry IS the split path (make_geometry
# derives split_bwd from the schedule spec) — and it matches the fused
# 1F1B baseline on the same tick diagonal.
# ---------------------------------------------------------------------------

def test_zero_bubble_default_matches_fused_1f1b():
    _run("""
        from repro.core.schedule import get_schedule
        assert get_schedule("zero-bubble-h1").split_bwd
        fused = loss_and_grads(*decoder_case(schedule="gpipe-1f1b",
                                             split_bwd=False))
        zb = loss_and_grads(*decoder_case(schedule="zero-bubble-h1"))
        check_split_parity(fused, zb, "zb-default-vs-fused-1f1b")
        print("OK zero-bubble default", float(zb[0]))
    """)


# ---------------------------------------------------------------------------
# The split composes with stage-aware remat: traced per-(stage, chunk)
# ckpt tables thread through the split stage body (the drain recomputes
# at l_ckpt=0 regardless — W-grad recompute is its own remat).
# ---------------------------------------------------------------------------

def test_split_backward_composes_with_traced_remat():
    _run("""
        TAB = ((2, 0, 1, 2), (1, 2, 0, 0))
        for kw in (dict(l_ckpt=2), dict(l_ckpt=2, ckpt_table=TAB)):
            fused = loss_and_grads(*decoder_case(split_bwd=False, **kw))
            split = loss_and_grads(*decoder_case(split_bwd=True, **kw))
            check_split_parity(fused, split, f"remat-{kw}")
        print("OK split x remat parity")
    """)


# ---------------------------------------------------------------------------
# Double-buffered hand-off: folding the pre-permute buffer before or
# after the ppermute is issued is the same program — bitwise, loss AND
# grads, with and without the split.
# ---------------------------------------------------------------------------

def test_overlap_handoff_bitwise():
    _run("""
        for split in (False, True):
            lo, no, go = loss_and_grads(*decoder_case(
                split_bwd=split, overlap_handoff=True))
            ls, ns, gs = loss_and_grads(*decoder_case(
                split_bwd=split, overlap_handoff=False))
            assert no == ns
            assert lo.tobytes() == ls.tobytes(), (float(lo), float(ls))
            for a, b in zip(go, gs):
                assert a.tobytes() == b.tobytes(), \\
                    f"hand-off buffering changed the math (split={split})"
        print("OK overlap hand-off bitwise")
    """)


# ---------------------------------------------------------------------------
# Host-side satellites (no subprocess needed).
# ---------------------------------------------------------------------------

def test_configure_latency_hiding_env_handling(monkeypatch):
    """Latency hiding is a property of the backend, not of XLA_FLAGS: the
    TPU compiler overlaps async collectives by default, the CPU backend
    never does — whatever the environment says."""
    import jax

    from repro.launch.mesh import latency_hiding_active
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_gpu_enable_latency_hiding_scheduler=true")
    assert not latency_hiding_active()       # the test session is on CPU
    monkeypatch.delenv("XLA_FLAGS")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert latency_hiding_active()


def test_production_mesh_validates_device_count():
    import pytest

    from repro.launch.mesh import make_production_mesh

    # the test session runs on far fewer than 256 devices
    with pytest.raises(ValueError, match="256 devices"):
        make_production_mesh()
    with pytest.raises(ValueError, match="512 devices"):
        make_production_mesh(multi_pod=True)


def test_pipeline_bubble_benchmark_meets_acceptance():
    """The committed benchmark geometry honors the acceptance criteria:
    ZB-H1's realized bubble strictly below 1F1B's and within 15% of the
    closed-form model bubble."""
    from benchmarks.paper_figures import pipeline_bubble

    rows = {r["schedule"]: r for r in pipeline_bubble()}
    zb, fb = rows["zero-bubble-h1"], rows["gpipe-1f1b"]
    assert zb["realized_bubble"] < fb["realized_bubble"]
    assert zb["realized_over_model"] <= 1.15
    assert zb["speedup_vs_1f1b"] > 1.0
    # the simulator's free-form W placement must beat (or meet) the
    # lockstep-realized bubble — it is the lower envelope
    assert zb["sim_bubble"] <= zb["realized_bubble"] + 1e-9
