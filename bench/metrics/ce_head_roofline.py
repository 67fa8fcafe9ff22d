"""The output head's share of its roofline, in %: the least time the chip
could take for the head's work in the traced steps (the larger of its
model FLOPs, ``flops.head_flops``, over chips x the bf16 peak and its
least HBM traffic, ``flops.head_bytes``, over chips x the HBM peak) over
the device time under the program's ``ce_head`` scope
(``bench/scopes.py``). Recomputation and padding are not counted in the
work, so the share cannot pass 100%. None where the trace holds no
scopes or no time under ``ce_head``."""

import flops

SCOPE = "ce_head"


def read(run):
    s = run.get("scopes")
    steps = run["steps"]
    if not s or not s["scoped"] or not s["layers_ms"][SCOPE] or not steps:
        return None
    cfg, chips = run["config"], run["chips"]
    least_s = max(
        sum(flops.head_flops(cfg, st["docs"]) for st in steps)
        / (chips * run["peak_flops"]),
        len(steps) * flops.head_bytes(cfg)
        / (chips * run["peak_bytes_per_s"]))
    # layers_ms is a step's time averaged over the chips
    return 100.0 * least_s / (1e-3 * s["layers_ms"][SCOPE] * len(steps))
