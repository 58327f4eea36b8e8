"""Mapping evaluations per second of device time of the engine's GA
programs, a ragged row counting one evaluation per group: (live rows -
ragged rows + their groups) x population x generations, from the program's
``engine.prepare`` counters of the traced (first) study, over the summed
device time of every ``_ga_program`` and ``_ga_program_ragged`` run in the
trace."""


def read(view):
    t = (view["counters"].get("timings") or [{}])[0]
    if "engine.prepare:ragged_rows" not in t:
        return None
    evals = (t.get("engine.prepare:rows", 0)
             - t["engine.prepare:ragged_rows"]
             + t.get("engine.prepare:groups", 0))
    tr = view["trace"]
    device_s = tr.program_s("_ga_program") + tr.program_s(
        "_ga_program_ragged")
    if not evals or device_s <= 0:
        return None
    return evals * view["counters"]["evals_per_row"] / device_s
