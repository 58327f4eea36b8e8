"""Host microseconds in the engine's chunk preparation per live engine
row, from the program's own ``engine.prepare`` span and its ``rows``
counter in each study's ``timings``, summed over the window's studies."""


def read(view):
    t = view["counters"].get("timings") or []
    rows = sum(s.get("engine.prepare:rows", 0) for s in t)
    if not rows:
        return None
    return sum(s.get("engine.prepare", 0.0) for s in t) / rows * 1e6
