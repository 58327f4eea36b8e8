"""Host microseconds building the ragged program variant's group tables
per ragged engine row: the program's ``engine.prepare.groups`` span over
its ``engine.prepare:ragged_rows`` counter, summed over the window's
studies."""


def read(view):
    t = view["counters"].get("timings") or []
    rows = sum(s.get("engine.prepare:ragged_rows", 0) for s in t)
    if not rows:
        return None
    return sum(s.get("engine.prepare.groups", 0.0) for s in t) / rows * 1e6
