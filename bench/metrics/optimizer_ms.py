"""Device time a step under the program's ``optimizer`` scope (AdamW and
the gradient clip's global norm), averaged over the chips, in ms
(``bench/scopes.py``; None where the trace holds no scopes)."""

SCOPE = "optimizer"


def read(run):
    s = run.get("scopes")
    return s["layers_ms"][SCOPE] if s and s["scoped"] else None
