"""A run with its timed path broken underneath comes out not correct, once
for every fault a training cell can have. The runs skip the harness's look
for a chip and drive the rest at the tiny cell's size on the CPU; each
fault is planted in the step's call or feed, which the checked steps and
the window share."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import harness
import tiny

BENCH = Path(__file__).resolve().parents[1]


def _frozen(call):
    """The step returns the state it was given."""
    def f(self, fn, batch):
        import jax
        import jax.numpy as jnp
        keep = jax.tree.map(jnp.copy, (self.params, self.opt))
        _, _, metrics = call(self, fn, batch)
        return (*keep, metrics)
    return f


def _unrefreshed(call):
    """The step updates the master weights but returns the parameters it
    computed with, not refreshed from them."""
    def f(self, fn, batch):
        import jax
        import jax.numpy as jnp
        keep = jax.tree.map(jnp.copy, self.params)
        _, opt, metrics = call(self, fn, batch)
        return keep, opt, metrics
    return f


def _half_batch(feed):
    """Half of the batch's tokens carry no target: the mean is over the
    rest."""
    def f(self, b):
        batch = feed(self, b)
        cap = batch["targets"].shape[-1]
        batch["targets"] = batch["targets"].at[..., cap // 2:].set(-1)
        return batch
    return f


def _loss_altered(call):
    """The loss is altered where the step produces it."""
    def f(self, fn, batch):
        params, opt, metrics = call(self, fn, batch)
        return params, opt, dict(metrics, loss=metrics["loss"] * 1.001)
    return f


FAULTS = {"frozen": ("_call", _frozen), "unrefreshed": ("_call", _unrefreshed),
          "half_batch": ("_feed", _half_batch),
          "loss_altered": ("_call", _loss_altered)}


def test_sound_run_is_correct():
    out = tiny.run()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(fault, monkeypatch):
    attr, plant = FAULTS[fault]
    monkeypatch.setattr(harness.Run, attr,
                        plant(getattr(harness.Run, attr)))
    out = tiny.run()
    assert not out["correct"], (fault, out["checks"])


EXCHANGE = """
import json, sys
sys.path[:0] = [{bench!r}, {tests!r}]
import jax
import tiny
if {drop}:
    jax.lax.ppermute = lambda x, axis_name, perm: x
print(json.dumps(tiny.run(chips=4)))
"""


@pytest.mark.parametrize("drop", [False, True])
def test_exchange_between_chips(drop):
    """2x2 mesh of virtual devices: sound, then with the stage hand-off
    (the ppermute between pipeline stages) left out."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = EXCHANGE.format(bench=str(BENCH), tests=str(BENCH / "tests"),
                           drop=drop)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is (not drop), out["checks"]
