"""Pipeline program auditor (repro.lint) tests.

Three layers:

* **Golden known-bad fixtures** — four deliberately broken programs/plans
  (forced f64 upcast, dropped donation, incomplete bucket key, broken
  ppermute ring), each tripping exactly its pass and none of the others.
* **Pinning regressions** — the auditor's findings on the real tree were
  fixed in this PR (bf16->f32 promotion in the streaming-CE fold /
  blocked-flash QK, non-donated error-feedback state in the AOT train
  step); these tests pin the fixes so they cannot silently regress.
* **Wiring** — the CompileCache lint hook (warn counts, error aborts
  before the cache insert), the CacheStore offline audit, and the
  ``python -m repro.lint`` CLI (clean registry sweep at ``--lint error``).

Anything needing more than the single real CPU device runs in a
subprocess with its own XLA_FLAGS (same convention as
test_runtime_pipeline.py).
"""

import copy
import hashlib
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import ClusterSpec, CostModel, PlannerConfig, plan_batch
from repro.core.plan import ExecutionPlan
from repro.core.schedule import stream_perm
from repro.launch.mesh import make_mesh
from repro.lint import (
    LintError,
    LintReport,
    ProgramArtifacts,
    available_passes,
    check_bucket_key_completeness,
    check_ppermute_perm,
    make_cache_lint,
    run_plan_checks,
    run_program_checks,
    stablehlo_donors,
)
from repro.lint.jaxpr_checks import iter_eqns

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _spec():
    from repro.core import ModelSpec
    return ModelSpec(name="t", n_layers=8, d_model=256, n_heads=8,
                     n_kv_heads=4, head_dim=32, d_ff=1024, vocab=512)


def _plan(d_p=4, d_s=4, **cfg):
    cm = CostModel(_spec(), ClusterSpec(d_p=d_p, d_s=d_s))
    return plan_batch(cm, [512, 384, 256, 256],
                      PlannerConfig(bucket_rounding=64, **cfg))


def _only_pass(report: LintReport, pass_name: str):
    """Assert every finding in ``report`` belongs to ``pass_name``."""
    assert report.findings, f"expected {pass_name} to fire: {report.summary()}"
    others = [f for f in report.findings if f.pass_name != pass_name]
    assert not others, f"unexpected cross-pass findings: {others}"


# ---------------------------------------------------------------------------
# golden fixture 1: forced f64 upcast
# ---------------------------------------------------------------------------


def test_golden_f64_fixture():
    def bad(x):
        return (x.astype(jnp.float64) * 2.0).sum()

    with jax.enable_x64(True):
        jx = jax.make_jaxpr(bad)(jnp.ones((8,), jnp.bfloat16))
    report = run_program_checks(ProgramArtifacts(jaxpr=jx))
    _only_pass(report, "program-f64")
    assert all(f.severity == "error" for f in report.findings)


def test_f64_hlo_text_tier():
    """Without a jaxpr the pass falls back to counting f64 types in HLO."""
    art = ProgramArtifacts(hlo="ENTRY %main { %x = f64[8]{0} parameter(0) }")
    report = run_program_checks(art)
    _only_pass(report, "program-f64")


# ---------------------------------------------------------------------------
# golden fixture 2: bf16 -> f32 upcast around a matmul
# ---------------------------------------------------------------------------


def test_golden_upcast_fixture():
    def bad(a, b):
        return jnp.einsum("td,vd->tv", a.astype(jnp.float32),
                          b.astype(jnp.float32))

    jx = jax.make_jaxpr(bad)(jnp.ones((4, 8), jnp.bfloat16),
                             jnp.ones((6, 8), jnp.bfloat16))
    report = run_program_checks(ProgramArtifacts(jaxpr=jx))
    _only_pass(report, "program-f32-upcast")


def test_upcast_detected_across_scan_scope():
    """The streaming-CE shape: one operand converted OUTSIDE the scan
    whose body runs the dot (the convert enters the body as an invar)."""
    def bad(h, wb):
        hf = h.astype(jnp.float32)

        def body(carry, w):
            return carry + jnp.einsum("td,vd->tv", hf,
                                      w.astype(jnp.float32)).sum(), None

        out, _ = jax.lax.scan(body, jnp.float32(0), wb)
        return out

    jx = jax.make_jaxpr(bad)(jnp.ones((4, 8), jnp.bfloat16),
                             jnp.ones((3, 6, 8), jnp.bfloat16))
    report = run_program_checks(ProgramArtifacts(jaxpr=jx))
    _only_pass(report, "program-f32-upcast")


def test_upcast_ignores_native_f32_operands():
    """A softmax-over-f32-stats matmul is NOT the convert-everything
    pattern; it must not be flagged."""
    def fine(p, v):
        return jnp.einsum("ts,sd->td", p, v.astype(jnp.float32))

    jx = jax.make_jaxpr(fine)(jnp.ones((4, 6), jnp.float32),
                              jnp.ones((6, 8), jnp.bfloat16))
    report = run_program_checks(ProgramArtifacts(jaxpr=jx))
    assert not report.by_pass("program-f32-upcast"), report.summary()


# ---------------------------------------------------------------------------
# golden fixture 3: dropped donation
# ---------------------------------------------------------------------------


def test_golden_dropped_donation_fixture():
    """StableHLO carries a deferred donor marker (``jax.buffer_donor``,
    the shard_map/train-step form) but the compiled HLO realized no
    alias for it.

    Synthetic texts: jax strips *lowering-time-unusable* donations from
    the StableHLO it emits, so the dropped-at-XLA shape this pass hunts
    can't be produced by a toy jit — only by a real program whose output
    type drifted, which is exactly what must not exist in the tree."""
    stablehlo = (
        "module @jit_f {\n"
        "  func.func public @main("
        "%arg0: tensor<2048xf32> {jax.buffer_donor = true}, "
        "%arg1: tensor<2048xf32> {jax.buffer_donor = true}) -> "
        "(tensor<2048xf32>, tensor<2048xbf16>) {\n"
        "  }\n}\n")
    hlo = ("HloModule jit_f, is_scheduled=true, "
           "input_output_alias={ {0}: (0, {}, may-alias) }, "
           "entry_computation_layout={(f32[2048]{0}, f32[2048]{0})->"
           "(f32[2048]{0}, bf16[2048]{0})}\n\nENTRY %main {}\n")
    report = run_program_checks(ProgramArtifacts(stablehlo=stablehlo,
                                                 hlo=hlo))
    dropped = report.by_pass("program-donation")
    assert len(dropped) == 1, report.summary()
    assert "silently dropped" in dropped[0].message
    assert "args [1]" in dropped[0].message
    others = [f for f in report.findings
              if f.pass_name != "program-donation"]
    assert not others, others


def test_donation_clean_when_aliased():
    def f(x):
        return x + 1

    lowered = jax.jit(f, donate_argnums=(0,)).lower(
        jnp.ones((2048,), jnp.float32))
    compiled = lowered.compile()
    art = ProgramArtifacts(stablehlo=lowered.as_text(),
                           hlo=compiled.as_text())
    report = run_program_checks(art)
    assert not report.by_pass("program-donation"), report.summary()


def test_donation_suspect_non_donated_state():
    """A large non-donated input whose exact type matches an un-aliased
    output is a donation suspect (the satellite-1 err-state shape)."""
    def f(x, state):
        return x + 1, state * 2

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        lowered = jax.jit(f, donate_argnums=(0,)).lower(
            jnp.ones((2048,), jnp.float32), jnp.ones((4096,), jnp.float32))
        compiled = lowered.compile()
    report = run_program_checks(ProgramArtifacts(
        stablehlo=lowered.as_text(), hlo=compiled.as_text()))
    sus = report.by_pass("program-donation")
    assert any("non-donated" in f.message for f in sus), report.summary()


# ---------------------------------------------------------------------------
# golden fixture 4: broken ppermute ring
# ---------------------------------------------------------------------------


def test_golden_broken_ppermute_ring():
    # colliding destination: two streams write device 1
    probs = check_ppermute_perm([(0, 1), (1, 1)], 2)
    assert any("destination" in p for p in probs)
    # out-of-range pair
    probs = check_ppermute_perm([(0, 2)], 2)
    assert any("out of range" in p for p in probs)
    # a chain is not a closed ring when the schedule demands one
    probs = check_ppermute_perm(stream_perm(4), 4, require_full=True)
    assert any("total permutation" in p for p in probs)
    # the real perms are valid
    assert check_ppermute_perm(stream_perm(4), 4) == []
    assert check_ppermute_perm(stream_perm(4, ring=True), 4,
                               require_full=True) == []


def test_stream_perm_is_the_executor_perm():
    """One definition of the hand-off permutation: the lint pass audits
    the same function the executor runs."""
    import inspect

    from repro.runtime import executor

    assert stream_perm(1) == [] and stream_perm(1, ring=True) == []
    assert stream_perm(4) == [(0, 1), (1, 2), (2, 3)]
    assert stream_perm(4, ring=True) == [(0, 1), (1, 2), (2, 3), (3, 0)]
    assert "stream_perm" in inspect.getsource(executor.ppermute_streams)


# ---------------------------------------------------------------------------
# bucket-key completeness: clean on the real key, fails per erased axis
# ---------------------------------------------------------------------------


def test_bucket_key_completeness_clean():
    assert check_bucket_key_completeness(_plan(), 4) == []


@pytest.mark.parametrize("axis,const", [
    ("schedule", "gpipe-1f1b"),
    ("v_stages", 1),
    ("ckpt", "u0"),
    ("split_bwd", False),
    ("dtype", "bfloat16"),
])
def test_bucket_key_incompleteness_detected(monkeypatch, axis, const):
    """Erase one axis from bucket_key() (freeze its field to a constant)
    and the completeness check must flag exactly that axis."""
    orig = ExecutionPlan.bucket_key

    def erased(self, d_s, **kw):
        return orig(self, d_s, **kw)._replace(**{axis: const})

    monkeypatch.setattr(ExecutionPlan, "bucket_key", erased)
    probs = check_bucket_key_completeness(_plan(), 4)
    assert any(a == axis for a, _ in probs), probs


def test_plan_checks_clean_on_real_plans():
    for schedule, v in [(None, 0), ("gpipe-1f1b", 0),
                        ("interleaved-1f1b", 2), ("zero-bubble-h1", 0)]:
        plan = _plan(schedule=schedule, v_stages=v)
        report = run_plan_checks(plan, 4, 4)
        assert report.ok, f"{schedule} v={v}: {report.summary()}"
        assert set(report.passes_run) == {
            p.name for p in available_passes("plan")}


def test_registry_plan_sweep_clean():
    """Every registry arch's planner output passes the plan audit at a
    tiny geometry (the jax-free half of the CI lint-programs job)."""
    from repro.configs import arch_names, get_arch

    for name in arch_names():
        cfg = get_arch(name).reduced()
        cm = CostModel(cfg.spec, ClusterSpec(d_p=2, d_s=2))
        plan = plan_batch(cm, [256, 256, 128, 384],
                          PlannerConfig(bucket_rounding=64))
        report = run_plan_checks(plan, 2, 2)
        assert report.ok, f"{name}: {report.summary()}"


# ---------------------------------------------------------------------------
# pinning regressions for the satellite fixes
# ---------------------------------------------------------------------------


def _kernel_report(fn, *args):
    jx = jax.make_jaxpr(fn)(*args)
    return run_program_checks(ProgramArtifacts(jaxpr=jx)), jx


def test_pin_streaming_ce_stats_no_upcast():
    from repro.kernels.ref import streaming_ce_stats

    h = jnp.ones((32, 16), jnp.bfloat16)
    w = jnp.ones((64, 16), jnp.bfloat16)
    t = jnp.zeros((32,), jnp.int32)
    report, jx = _kernel_report(
        lambda h, w, t: streaming_ce_stats(h, w, t, block_v=32), h, w, t)
    assert not report.by_pass("program-f32-upcast"), report.summary()
    # the fold still accumulates in f32: bf16 operands, f32 dot output
    dots = [e for e in iter_eqns(jx) if e.primitive.name == "dot_general"]
    assert dots and all(str(e.outvars[0].aval.dtype) == "float32"
                        for e in dots)
    assert all(str(iv.aval.dtype) == "bfloat16"
               for e in dots for iv in e.invars[:2])


def test_pin_streaming_ce_matches_reference():
    """preferred_element_type fix is numerics-preserving: bf16 products
    are exact in f32, so the streamed loss still matches the full-logits
    oracle."""
    from repro.kernels.ref import (cross_entropy_reference,
                                   streaming_cross_entropy)

    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(24, 16)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(50, 16)), jnp.bfloat16)
    t = jnp.asarray(rng.integers(0, 50, 24), jnp.int32)
    valid = jnp.asarray(rng.random(24) > 0.2)
    loss_s, n_s = streaming_cross_entropy(h, w, t, valid, block_v=16)
    loss_r, n_r = cross_entropy_reference(h, w, t, valid)
    np.testing.assert_allclose(float(loss_s), float(loss_r),
                               rtol=2e-5, atol=2e-5)
    assert float(n_s) == float(n_r)


def test_pin_blocked_flash_no_upcast_and_parity():
    from repro.kernels.ref import (blocked_flash_attention,
                                   flash_attention_reference)

    rng = np.random.default_rng(1)
    T, S, H, D = 16, 24, 2, 8
    q = jnp.asarray(rng.normal(size=(T, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(S, H, D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(S, H, D)), jnp.bfloat16)
    seg_q = jnp.zeros((T,), jnp.int32)
    seg_kv = jnp.zeros((S,), jnp.int32)
    pos_q = jnp.arange(T, dtype=jnp.int32) + (S - T)
    pos_kv = jnp.arange(S, dtype=jnp.int32)

    report, _ = _kernel_report(
        lambda *a: blocked_flash_attention(*a, block_kv=8),
        q, k, v, seg_q, seg_kv, pos_q, pos_kv)
    assert not report.by_pass("program-f32-upcast"), report.summary()
    out = blocked_flash_attention(q, k, v, seg_q, seg_kv, pos_q, pos_kv,
                                  block_kv=8)
    ref = flash_attention_reference(q, k, v, seg_q, seg_kv, pos_q, pos_kv)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_pin_err_state_donated():
    """The compress_pod_grads error-feedback state (arg 2) is donated:
    the program-donation finding this PR fixed must not come back."""
    from repro.optim import init_error_state, init_opt_state
    from repro.runtime import TrainStepBuilder, batch_struct, make_geometry

    from repro.configs import get_arch

    cfg = get_arch("gemma3-1b").reduced()
    mesh = make_mesh((1, 1), ("data", "model"))
    geom = make_geometry(cfg, mesh, n_chunks=2, cap=16, ctx_cap=16,
                         l_ckpt=0, compute_dtype=jnp.bfloat16)
    builder = TrainStepBuilder(cfg, mesh, geom, compress_pod_grads=True)
    params_shape = builder.abstract_params()
    opt_shape = jax.eval_shape(init_opt_state, params_shape)
    err_shape = jax.eval_shape(init_error_state, params_shape)
    bstruct = batch_struct(geom, 1)
    lowered = builder.build(params_shape).lower(params_shape, opt_shape,
                                                err_shape, bstruct)
    donors = stablehlo_donors(lowered.as_text())
    n_state = (len(jax.tree.leaves(params_shape))
               + len(jax.tree.leaves(opt_shape))
               + len(jax.tree.leaves(err_shape)))
    assert set(range(n_state)) <= donors, \
        f"state args 0..{n_state - 1} must all be donated, got {donors}"
    # the default (err=None) path still builds with donate_argnums=(0,1,2)
    b2 = TrainStepBuilder(cfg, mesh, geom)
    p2 = b2.abstract_params()
    b2.build(p2).lower(p2, jax.eval_shape(init_opt_state, p2), None,
                       batch_struct(geom, 1))


# ---------------------------------------------------------------------------
# CompileCache hook + CacheStore audit wiring
# ---------------------------------------------------------------------------


class _FakeCompiled:
    def __init__(self, text):
        self._text = text

    def as_text(self):
        return self._text


def test_compile_cache_lint_warn_counts():
    from repro.runtime.compile_cache import CompileCache

    logs = []
    cache = CompileCache(name="t", lint=make_cache_lint("warn",
                                                        log=logs.append))
    value = cache.get("k", lambda: _FakeCompiled(
        "ENTRY %main { %x = f64[8]{0} parameter(0) }"))
    assert isinstance(value, _FakeCompiled)
    assert cache.stats.lint_findings == 1
    assert cache.stats.lint_errors == 1
    assert any("[lint]" in line for line in logs)
    # warm hits are not re-audited
    cache.get("k", lambda: pytest.fail("should be cached"))
    assert cache.stats.lint_findings == 1


def test_compile_cache_lint_error_blocks_insert():
    from repro.runtime.compile_cache import CompileCache

    cache = CompileCache(name="t", lint=make_cache_lint("error"))
    with pytest.raises(LintError):
        cache.get("k", lambda: _FakeCompiled(
            "ENTRY %main { %x = f64[8]{0} parameter(0) }"))
    # the hazardous executable never entered the cache: a clean rebuild
    # under the same key compiles fresh and is accepted
    clean = cache.get("k", lambda: _FakeCompiled(
        "ENTRY %main { %x = f32[8]{0} parameter(0) }"))
    assert clean.as_text().startswith("ENTRY")
    assert cache.stats.misses == 2


def test_cache_store_audit(tmp_path):
    from repro.runtime.cache_store import CacheStore

    store = CacheStore(tmp_path, fingerprint={"v": "fp-test"})

    def write_entry(stem, blob, *, sha=None, orphan=False):
        meta = {"fingerprint": "fp-test", "key": stem,
                "payload_sha": sha or hashlib.sha256(blob).hexdigest(),
                "payload_bytes": len(blob), "created": 0.0}
        (tmp_path / f"{stem}.meta.json").write_text(json.dumps(meta))
        if not orphan:
            (tmp_path / f"{stem}.bin").write_bytes(blob)

    write_entry("good__fp", b"payload-bytes")
    write_entry("corrupt__fp", b"payload-bytes", sha="0" * 64)
    write_entry("orphan__fp", b"gone", orphan=True)

    rows = {r["entry"]: r for r in store.audit()}
    assert rows["good__fp.meta.json"]["problems"] == []
    assert any("sha256 mismatch" in p
               for p in rows["corrupt__fp.meta.json"]["problems"])
    assert any("orphan" in p
               for p in rows["orphan__fp.meta.json"]["problems"])


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _run_cli(args, timeout=900):
    env = dict(os.environ, PYTHONPATH=_SRC)
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-m", "repro.lint", *args],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)


def test_cli_cache_dir_audit(tmp_path):
    blob = b"ok-bytes"
    meta = {"fingerprint": "x", "key": "k",
            "payload_sha": hashlib.sha256(blob).hexdigest(),
            "payload_bytes": len(blob), "created": 0.0}
    (tmp_path / "e__f.meta.json").write_text(json.dumps(meta))
    (tmp_path / "e__f.bin").write_bytes(blob)
    r = _run_cli(["--cache-dir", str(tmp_path), "--lint", "error"])
    assert r.returncode == 0, r.stdout + r.stderr

    (tmp_path / "e__f.bin").write_bytes(b"flipped")
    r = _run_cli(["--cache-dir", str(tmp_path), "--lint", "error"])
    assert r.returncode == 1, r.stdout + r.stderr
    assert "sha256 mismatch" in r.stdout


def test_cli_plan_sweep_error_mode():
    """Plan-tier audit of the full registry is finding-free (the fast
    half of the CI zero-findings baseline)."""
    r = _run_cli(["--all", "--plan-only", "--lint", "error"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "[lint] clean" in r.stdout


def test_cli_program_audit_error_mode(tmp_path):
    """Full program audit (train + serve) of one representative arch is
    finding-free at --lint error, and emits the JSON report artifact."""
    out = tmp_path / "lint.json"
    r = _run_cli(["--arch", "gemma3-1b", "--target", "train,serve",
                  "--lint", "error", "--json", str(out)])
    assert r.returncode == 0, r.stdout + r.stderr
    rep = json.loads(out.read_text())
    assert rep["total_findings"] == 0
    progs = rep["subjects"][0]["programs"]
    assert progs["train"]["n_findings"] == 0
    assert progs["serve"]["n_findings"] == 0
    # both tiers really ran their passes
    assert "program-f32-upcast" in progs["train"]["passes_run"]
    assert "program-donation" in progs["train"]["passes_run"]
