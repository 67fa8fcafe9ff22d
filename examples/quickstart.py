"""Quickstart: plan + train a reduced llama3.2 with EPP on 8 fake CPU devices.

    PYTHONPATH=src python examples/quickstart.py
"""

import os
# 4 fake devices: this box has 1 core; more device threads than
# that trip XLA's CPU-collective rendezvous watchdog under load.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

from repro.configs import get_arch
from repro.launch.mesh import make_mesh
from repro.launch.train import TrainLoopConfig, train


def main():
    cfg = get_arch("llama3.2-3b").reduced()
    mesh = make_mesh((2, 2), ("data", "model"))
    loop = TrainLoopConfig(steps=4, global_batch=6, context=256,
                           dataset="github", compute_dtype="float32")
    _, _, history = train(cfg, mesh, loop)
    # convergence proper is proven by benchmarks fig13 / the equivalence
    # tests; 4 steps only sanity-check that training is stable.
    assert all(h["loss"] < 12.0 for h in history), "loss diverged"
    print("quickstart OK — loss", [round(h["loss"], 3) for h in history])


if __name__ == "__main__":
    main()
