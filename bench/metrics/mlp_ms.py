"""Device time a step under the program's ``mlp`` scope, forward,
backward and recomputation, averaged over the chips, in ms
(``bench/scopes.py``; None where the trace holds no scopes)."""


def read(run):
    s = run.get("scopes")
    return s["layers_ms"]["mlp"] if s and s["scoped"] else None
