"""Host microseconds in the engine's ``_prepare_chunk`` per live engine
row: the benchmark's span around the call, over the rows the prepare calls
were given (padding excluded).  ``_collect_chunk`` is left out: its span
holds the wait for the device as well as the host's unpacking."""


def read(view):
    rows = view["spans"].get("bench.engine.prepare", 0)
    if not rows:
        return None
    return view["trace"].span_s.get("bench.engine.prepare", 0.0) / rows * 1e6
