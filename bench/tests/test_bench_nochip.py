"""The measurement path refuses a CPU: non-zero exit, no result line."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

import harness

ROOT = Path(__file__).resolve().parents[2]


def test_devices_for_refuses_cpu():
    with pytest.raises(harness.NoChip):
        harness.devices_for(1)


def test_run_prints_no_result_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen3-4b.github-1k", "--seed", "5", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout
    assert "no TPU" in p.stderr
