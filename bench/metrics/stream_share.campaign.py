"""Share of the engine's live rows that drew a Generator stream of their
own: the program's ``engine.prepare.draws:streams`` counter over the
``engine.prepare:rows`` counter, summed over the window's studies, in
percent.  Rows that share a seed and R test share a stream, so the rest
derive their draws from one already drawn.  A program without the
counter reads nothing."""


def read(view):
    t = view["counters"].get("timings") or []
    if not t or any("engine.prepare.draws:streams" not in s for s in t):
        return None
    rows = sum(s.get("engine.prepare:rows", 0) for s in t)
    if not rows:
        return None
    return 100.0 * sum(s["engine.prepare.draws:streams"] for s in t) / rows
