"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: device busy time, device time per jitted program and per operation,
the benchmark's host spans, and the device's idle gaps attributed to the
host span that was open at the time.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation executed and ``XLA Modules`` one per program run
(named ``jit_<function>(<id>)``).  Host spans are the benchmark's own
``TraceAnnotation`` events, named ``bench.<what>``; ``bench.window`` marks
the traced window.  Host and device events share the trace's clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, List, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


@dataclasses.dataclass
class Reduced:
    window_s: float                    # length of the traced window
    busy_s: List[float]                # per device: union of op intervals
    module_s: Dict[str, float]         # program name -> device seconds
    op_s: Dict[str, float]             # operation name -> device seconds
    span_s: Dict[str, float]           # host span name -> seconds
    span_n: Dict[str, int]             # host span name -> count
    idle_by_span: Dict[str, float]     # innermost open span -> idle seconds

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s)

    def program_s(self, function: str) -> float:
        """Device seconds of every run of the jitted ``function``."""
        pat = re.compile(rf"^jit_{re.escape(function)}(\(|$)")
        return sum(v for k, v in self.module_s.items() if pat.match(k))


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clipped(line, w0: float, w1: float) -> List[Tuple[float, float, str]]:
    """The line's events inside [w0, w1] as (start, end, name), clipped."""
    out = []
    for ev in (line.events if line is not None else ()):
        s = ev.start_ns
        e = s + ev.duration_ns
        if e > w0 and s < w1:
            out.append((max(s, w0), min(e, w1), ev.name))
    return out


def _self_times(ops, modules) -> Dict[str, float]:
    """Device seconds per operation, less the operations nested in it (a
    loop's body runs inside the loop's event), keyed ``<program>/<op>``
    with the HLO instruction's name (``%fusion.12``) and the program whose
    run encloses it."""
    starts = [s for s, _, _ in modules]
    out: Dict[str, float] = {}
    stack: List[list] = []                # [end, key, own nanoseconds]

    def close(item):
        out[item[1]] = out.get(item[1], 0.0) + item[2] * 1e-9

    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        k = bisect.bisect_right(starts, s) - 1
        prog = re.sub(r"\(\d+\)$", "", modules[k][2]) \
            if k >= 0 and modules[k][1] >= s else "?"
        key = f"{prog}/{name.split(' = ', 1)[0][:80]}"
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, key, e - s])
    while stack:
        close(stack.pop())
    return out


def _attribute(gaps, spans) -> Dict[str, float]:
    """Idle seconds per innermost host span open at each gap's midpoint
    (``idle`` where none is open).  ``spans``: (start, end, name)."""
    spans = sorted(spans)
    out: Dict[str, float] = {}
    active: List[Tuple[float, float, str]] = []
    i = 0
    for g0, g1 in sorted(gaps):
        mid = 0.5 * (g0 + g1)
        while i < len(spans) and spans[i][0] <= mid:
            active.append(spans[i])
            i += 1
        active = [a for a in active if a[1] >= mid]
        # innermost = the latest start among the spans open at ``mid``
        name = max(active)[2] if active else "idle"
        out[name] = out.get(name, 0.0) + (g1 - g0) * 1e-9
    return out


def reduce(path: str) -> Reduced:
    """Reduce one ``.xplane.pb`` file (see the module docstring)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans: List[Tuple[float, float, str]] = []
    devices = []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name.startswith(SPAN_PREFIX):
                    s = ev.start_ns
                    spans.append((s, s + ev.duration_ns, name))
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not windows or not devices:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span or no TPU plane")
    w0, w1 = min(s for s, _ in windows), max(e for _, e in windows)
    inner = [sp for sp in spans if sp[2] != WINDOW_SPAN]
    span_s: Dict[str, float] = {}
    span_n: Dict[str, int] = {}
    for s, e, n in inner:
        span_s[n] = span_s.get(n, 0.0) + (e - s) * 1e-9
        span_n[n] = span_n.get(n, 0) + 1

    busy, module_s, op_s = [], {}, {}
    idle: Dict[str, float] = {}
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        modules = sorted(_clipped(lines.get("XLA Modules"), w0, w1))
        for s, e, name in modules:
            module_s[name] = module_s.get(name, 0.0) + (e - s) * 1e-9
        ops = _clipped(lines.get("XLA Ops"), w0, w1)
        for name, sec in _self_times(ops, modules).items():
            op_s[name] = op_s.get(name, 0.0) + sec
        merged = _union([(s, e) for s, e, _ in ops])
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
                if edges[k + 1] > edges[k]]
        for name, sec in _attribute(gaps, inner).items():
            idle[name] = idle.get(name, 0.0) + sec / len(devices)
    return Reduced(window_s=(w1 - w0) * 1e-9, busy_s=busy, module_s=module_s,
                   op_s=op_s, span_s=span_s, span_n=span_n,
                   idle_by_span=idle)


def top(d: Dict[str, float], n: int = 10) -> List[list]:
    """The ``n`` largest entries as ``[[name, seconds], ...]``."""
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
