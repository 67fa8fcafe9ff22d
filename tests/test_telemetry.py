"""Telemetry subsystem: timeline collection, atomic stats I/O, calibration
round-trip, drift detectors, and the deterministic straggler injector."""

import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from repro.core import CostModel, PlannerConfig, plan_batch
from repro.core.planner import estimate_plan_time
from repro.core.schedule import WGRAD_FRACTION
from repro.ft import StragglerInjector
from repro.telemetry import (Cusum, MixTracker, StepSample, StepTimeline,
                             atomic_write_json, fit_calibration,
                             plan_components, read_json, read_jsonl)
from repro.telemetry.calibrate import BWD_MULT, fit_stage_slowdowns


# ---------------------------------------------------------------------------
# StepTimeline
# ---------------------------------------------------------------------------

def test_timeline_ring_and_counters():
    tl = StepTimeline(capacity=4)
    for i in range(10):
        tl.record("step", i, wall_s=0.1)
    snap = tl.snapshot()
    assert snap["by_kind"]["step"] == 10          # counters never truncate
    assert snap["events"] == 10
    assert [e["step"] for e in tl.events()] == [6, 7, 8, 9]  # ring = tail


def test_timeline_bucket_ema_and_probe(tmp_path):
    tl = StepTimeline(capacity=16, spill_dir=str(tmp_path))
    tl.record_step(0, "bkA", 1.0, tokens=10, loss=2.0, per_stage_s=None,
                   probed=False)
    tl.record_step(1, "bkA", 2.0, tokens=10, loss=2.0,
                   per_stage_s=[0.5, 1.5], probed=True)
    snap = tl.snapshot()
    b = snap["per_bucket"]["bkA"]
    assert b["n"] == 2 and b["last_s"] == 2.0
    assert 1.0 < b["ema_s"] < 2.0                 # EMA between the samples
    assert snap["by_kind"]["probe"] == 1
    tl.close()
    lines = list(read_jsonl(tmp_path / "timeline-train.jsonl"))
    kinds = [ln["kind"] for ln in lines]
    assert "step" in kinds and "probe" in kinds


def test_timeline_spill_failure_never_raises(tmp_path):
    tl = StepTimeline(capacity=4, spill_dir=str(tmp_path))
    tl._spill.close()                             # sabotage the spill file
    tl.record("step", 0, wall_s=0.1)              # must not raise
    assert tl.snapshot()["dropped_spill_writes"] == 1
    tl.close()


# ---------------------------------------------------------------------------
# Atomic stats writes
# ---------------------------------------------------------------------------

def test_span_times_its_body_and_names_the_profiler_span(monkeypatch):
    """``span`` enters a ``TraceAnnotation`` (``StepTraceAnnotation`` with
    a ``step_num``) and reports the body's ``perf_counter`` reading."""
    from jax import profiler

    from repro.telemetry import span
    seen = []

    class Ann:
        def __init__(self, name, **args):
            seen.append((type(self).__name__, name, args))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            seen.append("exit")

    class StepAnn(Ann):
        pass

    monkeypatch.setattr(profiler, "TraceAnnotation", Ann)
    monkeypatch.setattr(profiler, "StepTraceAnnotation", StepAnn)
    with span("repro.plan_batch", bucket="b") as s:
        time.sleep(0.01)
        inside = s.elapsed()
    assert 0.01 <= inside <= s.seconds
    with span("repro.train_step", step_num=3):
        pass
    assert seen == [("Ann", "repro.plan_batch", {"bucket": "b"}), "exit",
                    ("StepAnn", "repro.train_step", {"step_num": 3}),
                    "exit"]
    with pytest.raises(ValueError):
        span("plan")


def test_span_times_without_jax(monkeypatch):
    from repro.telemetry import span
    monkeypatch.setitem(sys.modules, "jax", None)  # import jax fails
    with span("repro.materialize") as s:
        pass
    assert s.seconds >= 0.0


def test_atomic_write_and_read(tmp_path):
    p = tmp_path / "stats.json"
    atomic_write_json(p, {"a": 1})
    atomic_write_json(p, {"a": 2})
    assert read_json(p) == {"a": 2}
    assert [f for f in os.listdir(tmp_path) if f.startswith(".tmp-")] == []


def test_read_jsonl_skips_torn_tail(tmp_path):
    p = tmp_path / "log.jsonl"
    p.write_text('{"step": 0}\n{"step": 1}\n{"step": 2, "x": ')
    assert [r["step"] for r in read_jsonl(p)] == [0, 1]


def test_atomic_write_survives_writer_kill(tmp_path):
    """Regression: kill the writer mid-dump — the reader must only ever see
    the previous complete file, never a torn one."""
    target = tmp_path / "stats.json"
    atomic_write_json(target, {"generation": 0, "payload": "x" * 64})
    prog = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {repr(os.path.join(os.path.dirname(__file__),
                                              "..", "src"))})
        from repro.telemetry import atomic_write_json
        # a large payload keeps the dump window open long enough to be
        # killable; loop so the parent can kill at an arbitrary moment
        payload = "y" * (1 << 20)
        i = 1
        print("ready", flush=True)
        while True:
            atomic_write_json({repr(str(target))},
                              {{"generation": i, "payload": payload}})
            i += 1
    """)
    proc = subprocess.Popen([sys.executable, "-c", prog],
                            stdout=subprocess.PIPE)
    try:
        proc.stdout.readline()                    # writer is live
        time.sleep(0.2)                           # let some dumps land
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=10)
    finally:
        if proc.poll() is None:
            proc.kill()
    data = read_json(target)
    assert data is not None, "reader saw a torn stats file"
    assert data["payload"][0] in ("x", "y")
    assert len(data["payload"]) in (64, 1 << 20)  # a COMPLETE generation


# ---------------------------------------------------------------------------
# Calibration: round-trip + robustness
# ---------------------------------------------------------------------------

def _sample_plans(cm, n, seed=0, batch=8):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        lengths = [int(x) for x in np.clip(rng.lognormal(8, 1, size=batch),
                                           256, 32768)]
        out.append((lengths, plan_batch(cm, lengths, PlannerConfig())))
    return out


def test_calibration_round_trip_within_5pct(cost_model):
    """Synthesize step times from KNOWN component scales; the fit must
    recover the per-token forward/backward/wgrad times within 5%."""
    true = {"quad": 1.5, "lin": 0.8, "over": 1.0, "rec": 1.0, "comm": 1.3}
    rng = np.random.default_rng(1)
    samples = []
    for i, (lengths, plan) in enumerate(_sample_plans(cost_model, 16)):
        comp = plan_components(cost_model, plan)
        t = sum(true[k] * v for k, v in comp.items())
        samples.append(StepSample(
            step=i, measured_s=t * (1 + 0.01 * rng.standard_normal()),
            components=comp,
            sp_policy=plan.sp.policy if plan.sp is not None else "none"))
    cal = fit_calibration(samples, d_p=cost_model.cluster.d_p)
    cl = cost_model.cluster
    tf_true = cost_model.coeffs.alpha2 * true["lin"] / cl.n_devices
    assert abs(cal.t_f_per_token(cost_model) - tf_true) / tf_true < 0.05
    assert (abs(cal.t_b_per_token(cost_model) - BWD_MULT * tf_true)
            / (BWD_MULT * tf_true) < 0.05)
    tw_true = WGRAD_FRACTION * BWD_MULT * tf_true
    assert abs(cal.t_w_per_token(cost_model) - tw_true) / tw_true < 0.05
    assert abs(cal.scales["quad"] - true["quad"]) / true["quad"] < 0.05
    assert cal.residual_rel_rms < 0.05


def test_calibration_absorbs_unit_conversion(cost_model):
    """Measured wall SECONDS vs model units: the fit must still converge
    (scale-free active-column test + wide clip), with small residuals."""
    rng = np.random.default_rng(2)
    samples = []
    for i, (lengths, plan) in enumerate(_sample_plans(cost_model, 12)):
        comp = plan_components(cost_model, plan)
        t = 7.3 * sum(comp.values())              # pure unit change
        samples.append(StepSample(
            step=i, measured_s=t * (1 + 0.01 * rng.standard_normal()),
            components=comp,
            sp_policy=plan.sp.policy if plan.sp is not None else "none"))
    cal = fit_calibration(samples, d_p=cost_model.cluster.d_p)
    assert cal.residual_rel_rms < 0.05
    assert cal.scales["lin"] > 2.0                # absorbed the 7.3x


def test_calibration_robust_to_outliers(cost_model):
    true = {"quad": 1.2, "lin": 1.0, "over": 1.0, "rec": 1.0, "comm": 1.0}
    rng = np.random.default_rng(3)
    samples = []
    for i, (lengths, plan) in enumerate(_sample_plans(cost_model, 16)):
        comp = plan_components(cost_model, plan)
        t = sum(true[k] * v for k, v in comp.items())
        if i in (4, 11):                          # GC pause / noisy host
            t *= 5.0
        samples.append(StepSample(
            step=i, measured_s=t * (1 + 0.01 * rng.standard_normal()),
            components=comp,
            sp_policy=plan.sp.policy if plan.sp is not None else "none"))
    cal = fit_calibration(samples, d_p=cost_model.cluster.d_p)
    assert abs(cal.scales["quad"] - true["quad"]) / true["quad"] < 0.10


def test_calibration_apply_and_dict_round_trip(cost_model):
    samples = []
    for i, (lengths, plan) in enumerate(_sample_plans(cost_model, 8)):
        comp = plan_components(cost_model, plan)
        samples.append(StepSample(step=i, measured_s=1.4 * sum(comp.values()),
                                  components=comp, sp_policy="none"))
    cal = fit_calibration(samples, d_p=cost_model.cluster.d_p,
                          fingerprint="4x4:tiny", version=3)
    from repro.telemetry import CostCalibration
    back = CostCalibration.from_dict(cal.to_dict())
    assert back.version == 3 and back.fingerprint == "4x4:tiny"
    assert back.scales == pytest.approx(cal.scales)
    cm2 = back.apply(cost_model)
    assert cm2.coeffs.alpha1 == pytest.approx(
        cost_model.coeffs.alpha1 * cal.scales["quad"])


def test_calibration_drops_stale_mesh_slowdowns(cost_model):
    from repro.telemetry import CostCalibration
    cal = CostCalibration(version=1, scales={k: 1.0 for k in
                                             ("quad", "lin", "over", "rec",
                                              "comm")},
                          comm_scales={}, stage_slowdowns=[1.0, 2.0],
                          fingerprint="2x2:tiny")
    cm2 = cal.apply(cost_model)                   # d_p=4 != len 2
    assert cm2.stage_slowdowns is None


def test_fit_stage_slowdowns():
    probes = [[1.0, 1.0, 1.8, 1.0], [1.1, 0.9, 1.9, 1.0]]
    slow = fit_stage_slowdowns(probes, d_p=4)
    assert slow is not None
    assert slow[2] > 1.5
    assert slow[0] == slow[1] == slow[3] == 1.0   # snapped to baseline
    assert fit_stage_slowdowns([[1.0, 1.0]], d_p=2) is None


# ---------------------------------------------------------------------------
# Drift detectors
# ---------------------------------------------------------------------------

def test_cusum_detects_sustained_shift():
    c = Cusum(k=0.05, h=0.5)
    rng = np.random.default_rng(0)
    for _ in range(50):
        assert not c.update(float(0.02 * rng.standard_normal()))
    fired = any(c.update(0.3 + float(0.02 * rng.standard_normal()))
                for _ in range(10))
    assert fired
    c.reset()
    assert not c.update(0.0)


def test_mix_tracker_detects_phase_change():
    m = MixTracker(rel=0.3)
    rng = np.random.default_rng(0)
    for _ in range(10):
        assert not m.update([int(x) for x in rng.integers(100, 140, 8)])
    fired = any(m.update([int(x) for x in rng.integers(400, 520, 8)])
                for _ in range(6))
    assert fired
    m.settle()
    assert not m.update([int(x) for x in rng.integers(400, 520, 8)])


# ---------------------------------------------------------------------------
# Straggler injector
# ---------------------------------------------------------------------------

def test_injector_parse_and_determinism():
    inj = StragglerInjector.parse("2:2.5@3", 4, jitter=0.05, seed=7)
    assert inj.factors == {2: 2.5} and inj.start_step == 3
    assert not inj.active(2) and inj.active(3)
    a = inj.per_stage([1.0, 1.0, 1.0, 1.0], 5)
    b = inj.per_stage([1.0, 1.0, 1.0, 1.0], 5)
    assert a == b                                 # (seed, step) determinism
    assert a[1] > 2.0                             # stage 2 (1-based) slowed
    assert inj.wall(1.0, 5) > 2.0                 # worst factor gates wall
    assert inj.wall(1.0, 0) == pytest.approx(
        float(1.0 + 0.05 * np.random.default_rng((7, 0)).standard_normal(1)[0]))


def test_injector_rejects_bad_stage():
    with pytest.raises(ValueError):
        StragglerInjector.parse("5:2.0", 4)
