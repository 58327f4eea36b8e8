"""Program-side spans and counters.

A span names a piece of host work where it happens::

    with tracing.span("engine.prepare", rows=len(rows), chunks=1):
        ...

It always opens a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``,
which lands on the host plane of a profiler trace, on the same clock as the
device's events, and costs well under a microsecond with no profiler
session.  Inside :func:`recording`, the span also adds its
``time.perf_counter`` seconds to the recorder's dict under ``<name>`` and
each count under ``<name>:<counter>``.  Recorders nest: a span adds to
every recorder active in its context, so a caller recording a whole phase
sees what an inner ``recording`` of the same work sees.

While a recorder is active, one ``jax.monitoring`` listener adds
``jax:compiles`` and ``jax:compile_s`` for every backend compile event in
the recording context (JAX records one per executable built, a persistent
cache hit included, with its load time).

Recorders are held in a ``contextvars.ContextVar``: a thread started inside
a ``recording`` block does not inherit it unless it runs in a copy of the
context.  The spans and counters are listed in docs/mapper.md ("Spans and
counters").
"""
from __future__ import annotations

import contextlib
import contextvars
import threading
import time
from typing import Dict, Iterator, Tuple

import jax

PREFIX = "repro."
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_SINKS: contextvars.ContextVar[Tuple[Dict[str, float], ...]] = \
    contextvars.ContextVar("repro_tracing_sinks", default=())
_LISTENER_LOCK = threading.Lock()
_listening = False


def _add(sinks, key: str, value: float) -> None:
    for sink in sinks:
        sink[key] = sink.get(key, 0) + value


class span:
    """A host span ``repro.<name>`` on the trace, recorded under ``name``
    (and ``name:<counter>`` for each count) by every active recorder.  A
    context manager; ``seconds`` holds the span's length once it has
    closed."""

    __slots__ = ("name", "counts", "seconds", "_annotation", "_sinks", "_t0")

    def __init__(self, name: str, **counts: float):
        self.name = name
        self.counts = counts
        self.seconds = 0.0
        self._annotation = jax.profiler.TraceAnnotation(PREFIX + name)

    def __enter__(self) -> "span":
        self._sinks = _SINKS.get()
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        if self._sinks:
            _add(self._sinks, self.name, self.seconds)
            for counter, n in self.counts.items():
                _add(self._sinks, f"{self.name}:{counter}", n)


def _on_duration(event: str, duration: float, **_) -> None:
    if event == COMPILE_EVENT:
        sinks = _SINKS.get()
        _add(sinks, "jax:compiles", 1)
        _add(sinks, "jax:compile_s", duration)


def _listen() -> None:
    global _listening
    with _LISTENER_LOCK:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _listening = True


@contextlib.contextmanager
def recording(sink: Dict[str, float]) -> Iterator[Dict[str, float]]:
    """Record spans and compile counters into ``sink`` for the block."""
    _listen()
    token = _SINKS.set(_SINKS.get() + (sink,))
    try:
        yield sink
    finally:
        _SINKS.reset(token)
