"""Seconds per study in the engine sweep of every (model, variant) pair:
the study's own ``flex_sweep`` timer."""


def read(view):
    t = view["counters"].get("timings") or []
    if not t:
        return None
    return sum(s["flex_sweep"] for s in t) / len(t)
