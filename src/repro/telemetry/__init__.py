"""Telemetry + calibration + online re-planning (the control plane).

Three layers, consumed bottom-up by the launch drivers:

* :mod:`~repro.telemetry.timeline` — ``StepTimeline``: ring-buffered event
  recorder with JSONL spill and always-on per-bucket EMA counters.
* :mod:`~repro.telemetry.calibrate` — ``CostCalibration``: robust fit of
  measured step times back onto ``core/costs.py`` terms, plus the CUSUM /
  length-mix drift detectors.
* :mod:`~repro.telemetry.replan` — ``ReplanController``: drift → fit →
  re-solve → hysteresis-gated hot-swap at a step boundary (with off-thread
  precompile, plan-lint rejection, and per-mesh calibration persistence).

Pure Python/NumPy — importable without JAX, like ``repro.core``.
"""

from .calibrate import (CostCalibration, Cusum, MixTracker, StepSample,
                        fit_calibration, fit_stage_slowdowns,
                        plan_components, predicted_work)
from .replan import ReplanConfig, ReplanController, ReplanDecision
from .stats_io import atomic_write_json, read_json, read_jsonl
from .timeline import StepEvent, StepTimeline, span

__all__ = [
    "CostCalibration", "Cusum", "MixTracker", "StepSample",
    "fit_calibration", "fit_stage_slowdowns", "plan_components",
    "predicted_work",
    "ReplanConfig", "ReplanController", "ReplanDecision",
    "atomic_write_json", "read_json", "read_jsonl",
    "StepEvent", "StepTimeline", "span",
]
