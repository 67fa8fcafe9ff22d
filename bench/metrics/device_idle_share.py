"""1 - the union of device-operation intervals over the traced window,
averaged over the chips, in %."""


def read(run):
    t = run.get("trace")
    return None if t is None else 100.0 * t["idle_share"]
