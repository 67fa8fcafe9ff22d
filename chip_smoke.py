#!/usr/bin/env python3
"""Chip smoke test: the EPP trainer's main path on a TPU, end to end.

    python chip_smoke.py              # one chip: 3 training steps
    python chip_smoke.py --four-chip  # four chips: 2x2 pipeline vs 1x1

Drives ``repro.launch.train.train()`` — the planner, the plan-bucket
compile cache and the shard_map'd EPP step — on llama3.2-3b at every
published width (d_model 3072, 24/8 heads of 128, d_ff 8192, vocab 128256,
tied embeddings) with the depth cut to ``N_LAYERS``; weights are random
from ``SEED``. The batch is the heavy-tailed github length mix at a context
of ``CONTEXT`` tokens, trained in bf16 with AdamW under the planner's
default stage-aware checkpointing, which raises recomputation until the
compiled step fits the device.

One chip: fails unless JAX runs on a TPU, every loss is finite and the
step-0 loss is within 1.0 of ln(vocab) (the loss of random weights).
``--four-chip`` runs the same model, batch and seed on a 2x2 mesh
(2 pipeline stages x 2-way SP/ZeRO) over all four devices and the same
step on a 1x1 mesh on device 0; it fails unless the step-0 losses agree
within 0.02, the 2x2 losses are finite, and every device's peak memory in
the 2x2 run is within 2x of every other's.

The last line of standard output is one JSON object, printed only when
every check passed.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "llama3.2-3b"
N_LAYERS = 2          # 3 layers exceed the v5e's 16 GB at this batch
BATCH = 2
CONTEXT = 8192
SEED = 2
STEPS = 3
LOSS_TOL_LN_V = 1.0
LOSS_TOL_MESHES = 0.02
PEAK_SPREAD = 2.0


def _fail(msg: str) -> int:
    print(f"FAIL: {msg}", file=sys.stderr)
    return 1


def _model():
    import dataclasses

    from repro.configs import get_arch
    cfg = get_arch(ARCH)
    full = cfg.spec.n_layers
    cfg = dataclasses.replace(
        cfg, spec=dataclasses.replace(cfg.spec, n_layers=N_LAYERS))
    s = cfg.spec
    print(f"[model] {ARCH}: d_model {s.d_model}, {s.n_heads}/{s.n_kv_heads} "
          f"heads x {s.head_dim}, d_ff {s.d_ff}, vocab {s.vocab}, tied; "
          f"depth cut {full} -> {N_LAYERS} layers", flush=True)
    return cfg


def _train(cfg, mesh, steps: int, tag: str):
    """One ``train()`` run; prints and returns its per-step history."""
    from repro.launch.train import TrainLoopConfig, train
    loop = TrainLoopConfig(steps=steps, global_batch=BATCH, context=CONTEXT,
                           dataset="github", seed=SEED,
                           compute_dtype="bfloat16", probe_every=1)
    t0 = time.perf_counter()
    _, _, hist = train(cfg, mesh, loop,
                       log=lambda m: print(f"[{tag}] {m}", flush=True))
    total = time.perf_counter() - t0
    cc = hist[-1]["compile_cache"]
    print(f"[{tag}] compile_s {cc['compile_seconds']} over {cc['misses']} "
          f"bucket(s); run_s {total:.3f}", flush=True)
    for h in hist:
        print(f"[{tag}] step {h['step']} loss {h['loss']:.6f} "
              f"wall_s {h['wall']:.6f} tokens {int(h['tokens'])} "
              f"slots {h['slots']}", flush=True)
    return hist


def _peaks(devices):
    return [d.memory_stats()["peak_bytes_in_use"] for d in devices]


def _check_losses(hist, tag: str, vocab: int):
    bad = [h["loss"] for h in hist if not math.isfinite(h["loss"])]
    if bad:
        return f"{tag}: non-finite loss {bad}"
    ln_v = math.log(vocab)
    if abs(hist[0]["loss"] - ln_v) > LOSS_TOL_LN_V:
        return (f"{tag}: step-0 loss {hist[0]['loss']:.4f} is not within "
                f"{LOSS_TOL_LN_V} of ln(vocab) = {ln_v:.4f}")
    return None


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="2x2 pipeline over four devices vs 1x1 on one")
    args = ap.parse_args()
    try:
        import jax

        from repro.launch.mesh import enable_compile_cache, make_mesh
    except ImportError as e:
        return _fail(f"cannot import the trainer ({e}); run from the "
                     f"repository root")
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return _fail(f"JAX found no TPU (platform {dev.platform!r})")
    need = 4 if args.four_chip else 1
    if len(devices) < need:
        return _fail(f"need {need} devices, JAX found {len(devices)}")
    print(f"[device] {dev.device_kind} x{len(devices)} "
          f"(platform {dev.platform})", flush=True)
    print(f"[compile-cache] {enable_compile_cache()}", flush=True)
    cfg = _model()
    print(f"[batch] github x{BATCH} at context {CONTEXT}, seed {SEED}, "
          f"bf16, AdamW, the planner's remat", flush=True)

    if not args.four_chip:
        hist = _train(cfg, make_mesh((1, 1), ("data", "model")), STEPS,
                      "1x1")
        print(f"[memory] peak_bytes_in_use {_peaks([dev])[0]}", flush=True)
        err = _check_losses(hist, "1x1", cfg.spec.vocab)
        if err:
            return _fail(err)
    else:
        quad = devices[:4]
        hist4 = _train(cfg, make_mesh((2, 2), ("data", "model"),
                                      devices=quad), STEPS, "2x2")
        peaks = _peaks(quad)
        print(f"[memory] 2x2 peak_bytes_in_use per device {peaks}",
              flush=True)
        hist1 = _train(cfg, make_mesh((1, 1), ("data", "model"),
                                      devices=quad[:1]), 1, "1x1")
        delta = abs(hist4[0]["loss"] - hist1[0]["loss"])
        print(f"[compare] step-0 loss 2x2 {hist4[0]['loss']:.6f} vs 1x1 "
              f"{hist1[0]['loss']:.6f}: delta {delta:.6f}", flush=True)
        for err in (_check_losses(hist4, "2x2", cfg.spec.vocab),
                    _check_losses(hist1, "1x1", cfg.spec.vocab)):
            if err:
                return _fail(err)
        if not delta <= LOSS_TOL_MESHES:
            return _fail(f"2x2 and 1x1 step-0 losses differ by {delta:.6f} "
                         f"(> {LOSS_TOL_MESHES})")
        if max(peaks) > PEAK_SPREAD * min(peaks):
            return _fail(f"2x2 per-device peaks {peaks} spread beyond "
                         f"{PEAK_SPREAD}x")
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
