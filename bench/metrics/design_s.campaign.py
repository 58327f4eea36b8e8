"""Seconds per study in the fixed-config designs and the frozen-design
replay: the study's own ``design_fixed`` and ``replay_frozen`` timers."""


def read(view):
    t = view["counters"].get("timings") or []
    if not t:
        return None
    return sum(s["design_fixed"] + s["replay_frozen"] for s in t) / len(t)
