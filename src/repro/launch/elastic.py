"""Elastic rescale demonstration: EPP is natively elastic because plans are
functions of (mesh, workload), not baked state.

Shrink/grow flow:
  1. checkpoint on the old mesh (CheckpointManager — reshard-on-load),
  2. build a new mesh (lost pod => fewer devices, or scale-out),
  3. re-plan with the new ClusterSpec (the solver re-balances chunks, the
     ILP re-solves checkpointing for the new memory budget),
  4. restore parameters with the new shardings and continue.

The compile-cache store rides along: every phase shares one ``cache_dir``.
The shrink phase's mesh change invalidates the store fingerprint, so its
buckets cold-compile (stale entries are *skipped*, never loaded wrong);
the grow-back phase returns to the original topology and warm-starts the
phase-1 buckets with zero fresh compiles.

``python -m repro.launch.elastic --arch llama3.2-3b`` runs the whole cycle
at reduced scale on CPU (4 fake devices -> 2 -> 4) and verifies the loss
continues smoothly across both restarts. See examples/elastic_restart.py.
"""

from __future__ import annotations

import argparse


def _assert_loss_continuity(prev_hist, next_hist, phase: str,
                            rel_tol: float = 0.25) -> None:
    """The restarted run must CONTINUE the previous one: it resumes right
    after a step the previous phase RAN (the last checkpointed one — not
    necessarily the last step, when steps isn't a multiple of ckpt_every)
    and its first loss stays close to that step's (a scrambled restore
    shows up as a jump back toward the init loss)."""
    nxt = next_hist[0]
    by_step = {h["step"]: h for h in prev_hist}
    prev = by_step.get(nxt["step"] - 1)
    assert prev is not None, \
        f"{phase}: resumed at step {nxt['step']}, but the previous phase " \
        f"never ran step {nxt['step'] - 1} (ran " \
        f"{prev_hist[0]['step']}..{prev_hist[-1]['step']})"
    rel = abs(nxt["loss"] - prev["loss"]) / max(prev["loss"], 1e-9)
    assert rel < rel_tol, \
        f"{phase}: loss discontinuity across restart — " \
        f"{prev['loss']:.4f} (step {prev['step']}) -> " \
        f"{nxt['loss']:.4f} (step {nxt['step']}) ({rel:.1%})"
    print(f"[{phase}] loss continuity OK: {prev['loss']:.4f} -> "
          f"{nxt['loss']:.4f} ({rel:.2%})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--replan", default="observe",
                    choices=["off", "observe", "auto"],
                    help="online re-planning across the elastic phases: the "
                         "shared telemetry dir persists calibrations per "
                         "mesh fingerprint, so the shrink phase (foreign "
                         "fingerprint) forces an immediate elastic re-solve "
                         "and the grow phase warm-starts phase 1's "
                         "calibration")
    args = ap.parse_args()

    import os
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=4")
    import shutil

    from repro.configs import get_arch
    from repro.launch.mesh import CHECKOUT_ROOT, make_mesh
    from repro.launch.train import TrainLoopConfig, train

    cfg = get_arch(args.arch).reduced()
    # a fixed in-checkout directory (the compile-cache path is part of the
    # cache key), emptied first so phase 1 always starts cold
    d = CHECKOUT_ROOT / "runs" / "elastic"
    shutil.rmtree(d, ignore_errors=True)
    ckpt = os.path.join(d, "ckpt")
    cache = os.path.join(d, "compile_cache")
    tele = os.path.join(d, "telemetry")
    common = dict(global_batch=6, context=256, ckpt_dir=ckpt,
                  ckpt_every=2, cache_dir=cache,
                  compute_dtype="float32",
                  replan=args.replan,
                  # one telemetry dir across every phase: calibrations
                  # persist keyed by mesh fingerprint, which is what
                  # makes the shrink/grow behavior below observable
                  telemetry_dir=(tele if args.replan != "off" else None),
                  replan_min_samples=2, replan_background=False)
    loop = TrainLoopConfig(steps=args.steps, **common)
    mesh_a = make_mesh((2, 2), ("data", "model"))
    print(f"== phase 1: mesh {dict(mesh_a.shape)} ==")
    _, _, hist_a = train(cfg, mesh_a, loop)
    if args.replan != "off":
        rep_a = hist_a[-1].get("replan", {})
        assert rep_a.get("calibration_version", 0) >= 1, \
            f"phase 1 should adopt a bootstrap calibration: {rep_a}"

    # "lose half the machine": restart on a (1, 2) mesh. The mesh
    # change flips the store fingerprint, so phase 1's persisted
    # buckets are skipped as stale and this phase cold-compiles.
    mesh_b = make_mesh((1, 2), ("data", "model"))
    loop_b = TrainLoopConfig(steps=args.steps + 2, resume=True,
                             **common)
    print(f"== phase 2 (elastic shrink): mesh {dict(mesh_b.shape)} ==")
    _, _, hist_b = train(cfg, mesh_b, loop_b)
    _assert_loss_continuity(hist_a, hist_b, "shrink")
    if args.replan != "off":
        # the (1,2) mesh has no calibration in the shared store — the
        # controller must force an immediate elastic re-solve instead
        # of replaying the bootstrap plan
        rep_b = hist_b[-1].get("replan", {})
        assert "elastic" in rep_b.get("triggers", {}), \
            f"shrink phase should force an elastic re-solve: {rep_b}"
    store_b = hist_b[-1]["cache_store"]
    # phase 1's entries sit in the shared store under a foreign
    # fingerprint and must never be loaded. That is observable two
    # ways: a stale skip when a bucket key collides across the two
    # topologies, or — when the planner legitimately picks different
    # geometry per mesh (d_p=1 solves gpipe-1f1b where d_p=2 solves
    # zero-bubble-h1, so the keys never collide) — foreign entries
    # coexisting with zero warm loads.
    assert (store_b["stale_skips"] >= 1
            or store_b["entries"] > store_b["entries_current_fingerprint"]), \
        f"shrink phase should see phase 1's buckets only as foreign, " \
        f"store report: {store_b}"
    assert store_b["loads"] == 0, store_b
    assert hist_b[-1]["compile_cache"]["warm_hits"] == 0

    # the lost half comes back: grow to the original (2, 2) mesh.
    # Same topology fingerprint as phase 1 => repeated buckets
    # warm-start from the store with zero fresh compiles.
    loop_c = TrainLoopConfig(steps=args.steps + 4, resume=True,
                             **common)
    print(f"== phase 3 (elastic grow): mesh {dict(mesh_a.shape)} ==")
    _, _, hist_c = train(cfg, mesh_a, loop_c)
    _assert_loss_continuity(hist_b, hist_c, "grow")
    cc = hist_c[-1]["compile_cache"]
    assert cc["warm_hits"] >= 1, \
        f"grow phase should warm-start phase 1's buckets, got {cc}"
    if args.replan != "off":
        # back on the original topology: phase 1's calibration warm-
        # starts (same fingerprint), so no elastic re-solve is forced
        rep_c = hist_c[-1].get("replan", {})
        assert rep_c.get("calibration_version", 0) >= 1, \
            f"grow phase should warm-start phase 1's calibration: {rep_c}"
        assert "elastic" not in rep_c.get("triggers", {}), \
            f"grow phase must not force an elastic re-solve: {rep_c}"
    print("elastic restart OK (shrink cold-compiled, grow "
          f"warm-started {cc['warm_hits']} bucket(s), "
          f"{cc['misses']} cold)")


if __name__ == "__main__":
    main()
