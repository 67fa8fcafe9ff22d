"""Real tokens over the chunk slots (chunks x capacity) of the buckets
that the window's steps ran, in %."""


def read(run):
    slots = sum(s["slots"] for s in run["steps"])
    if not slots:
        return None
    return 100.0 * sum(s["real"] for s in run["steps"]) / slots
