"""Share of the engine sweep the host spends blocked on the device: the
program's ``engine.wait`` span over the study's ``flex_sweep`` phase,
summed over the window's studies.  Near 0 the host sets the pipeline's
pace; it rises as the device does."""


def read(view):
    t = view["counters"].get("timings") or []
    if not t or any("engine.wait" not in s for s in t):
        return None
    sweep = sum(s["flex_sweep"] for s in t)
    if sweep <= 0:
        return None
    return 100.0 * sum(s["engine.wait"] for s in t) / sweep
