"""Leaf-op device time under ``rematted_computation`` (the backward's
recomputation) over the traced window's leaf-op time, in %
(``bench/scopes.py``; None where the trace holds no scopes)."""


def read(run):
    s = run.get("scopes")
    if not s or not s["scoped"] or not s["leaf_ms"]:
        return None
    return 100.0 * s["remat_ms"] / s["leaf_ms"]
