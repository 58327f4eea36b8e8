"""The traced window, and the host spans the benchmark wraps around calls
into the program's layers.

In a traced run each wrapped call opens a ``jax.profiler.TraceAnnotation``
named ``bench.<layer>.<call>`` while the trace records, so the trace
reduction can attribute the device's idle gaps to what the host was doing
and sum the host time of a layer; a counter records the work each call was
given.  In an untraced run nothing is wrapped.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

from lib.trace import WINDOW_SPAN


class Spans:
    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.recording = False
        self.counts: Dict[str, int] = {}
        self._undo = []
        self._window = None

    def start(self, log_dir: str) -> None:
        """Start the trace and open the ``bench.window`` span."""
        import jax
        from jax.profiler import TraceAnnotation
        # the Python tracer records every call: it slows the host path and
        # makes the trace fifty times larger
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        self._window = TraceAnnotation(WINDOW_SPAN)
        self._window.__enter__()
        self.recording = True

    def stop(self) -> None:
        """Close the window span and stop the trace; a generator may end
        the traced window before its own window ends.  Idempotent."""
        if self.recording:
            import jax
            self._window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.recording = False

    def wrap(self, owner, attr: str, name: str,
             count: Optional[Callable] = None) -> None:
        """Replace ``owner.attr`` by a wrapper that opens span ``name`` and
        adds ``count(*args, **kwargs)`` to ``counts[name]``."""
        if not self.enabled:
            return
        from jax.profiler import TraceAnnotation

        inner = getattr(owner, attr)

        def wrapper(*args, **kwargs):
            if not self.recording:
                return inner(*args, **kwargs)
            if count is not None:
                self.counts[name] = (self.counts.get(name, 0)
                                     + count(*args, **kwargs))
            with TraceAnnotation(name):
                return inner(*args, **kwargs)

        setattr(owner, attr, wrapper)
        self._undo.append((owner, attr, inner))

    def close(self) -> None:
        for owner, attr, inner in reversed(self._undo):
            setattr(owner, attr, inner)
        self._undo.clear()


def wrap_engine(spans: Spans) -> None:
    """The engine's host path: ``_prepare_chunk`` (counting the live rows
    it is given) and ``_collect_chunk``."""
    from repro.core import engine
    spans.wrap(engine, "_prepare_chunk", "bench.engine.prepare",
               count=lambda rows, *a, **k: len(rows))
    spans.wrap(engine, "_collect_chunk", "bench.engine.collect")
