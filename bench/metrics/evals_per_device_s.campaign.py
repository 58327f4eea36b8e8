"""Mapping evaluations per second of device time of the engine program:
live rows x population x generations, over the summed device time of
every ``_ga_program`` run in the trace."""


def read(view):
    rows = view["spans"].get("bench.engine.prepare", 0)
    device_s = view["trace"].program_s("_ga_program")
    if not rows or device_s <= 0:
        return None
    return rows * view["counters"]["evals_per_row"] / device_s
