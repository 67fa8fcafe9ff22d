"""End-to-end driver (deliverable b): train a ~100M-param model for a few
hundred steps on a skewed variable-length corpus with the full InfiniPipe
stack — planner (chunking + grouping + ckpt ILP) overlapped with the
executor, checkpointing every 50 steps.

    PYTHONPATH=src python examples/train_varlen_epp.py --steps 300
"""

import argparse
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

from repro.configs import get_arch
from repro.launch.mesh import make_mesh
from repro.launch.train import TrainLoopConfig, train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    args = ap.parse_args()
    # ~100M params: gemma3 family reduced to 8 layers x 512 width
    cfg = get_arch("gemma3-1b").reduced(n_layers=8, d_model=512, n_heads=8,
                                        head_dim=64, vocab=8192)
    mesh = make_mesh((2, 2), ("data", "model"))
    loop = TrainLoopConfig(steps=args.steps, global_batch=16, context=2048,
                           dataset="github", ckpt_dir="runs/quickckpt",
                           ckpt_every=50, compute_dtype="float32")
    _, _, hist = train(cfg, mesh, loop)
    print(f"final loss {hist[-1]['loss']:.4f} over {len(hist)} steps")


if __name__ == "__main__":
    main()
