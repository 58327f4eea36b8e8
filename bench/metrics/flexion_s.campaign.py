"""Seconds per study in the H-F and W-F estimators: the study's own
``flexion`` timer."""


def read(view):
    t = view["counters"].get("timings") or []
    if not t or any("flexion" not in s for s in t):
        return None
    return sum(s["flexion"] for s in t) / len(t)
