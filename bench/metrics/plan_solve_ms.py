"""Mean host time of one ``plan_batch`` call in the window, in ms."""


def read(run):
    ms = [s["solve_ms"] for s in run["steps"]]
    return sum(ms) / len(ms) if ms else None
