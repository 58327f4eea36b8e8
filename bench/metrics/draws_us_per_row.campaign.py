"""Host microseconds per live engine row in the per-row part of the
engine's chunk preparation (map space, initial population, GA draw
streams): the program's ``engine.prepare.draws`` span over the
``engine.prepare:rows`` counter, summed over the window's studies."""


def read(view):
    t = view["counters"].get("timings") or []
    rows = sum(s.get("engine.prepare:rows", 0) for s in t)
    if not rows:
        return None
    return sum(s.get("engine.prepare.draws", 0.0) for s in t) / rows * 1e6
