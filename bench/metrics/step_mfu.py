"""Model FLOPs of the traced steps (``bench/flops.py``) over the traced
window's time, over chips x the published bf16 peak, in %."""


def read(run):
    t = run.get("trace")
    if t is None or not run.get("flops"):
        return None
    return 100.0 * run["flops"] / (t["window_s"] * run["chips"]
                                   * run["peak_flops"])
