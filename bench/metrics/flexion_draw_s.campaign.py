"""Seconds per study in the flexion estimators' host tile draws: the
program's ``flexion.draw`` span in each study's ``timings``."""


def read(view):
    t = view["counters"].get("timings") or []
    if not t or any("flexion.draw" not in s for s in t):
        return None
    return sum(s["flexion.draw"] for s in t) / len(t)
