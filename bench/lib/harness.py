"""One run of one benchmark cell: set-up, the measured window, the traced
reduction, and the comparison that decides ``correct``.

Everything is found by name from ``BENCHMARK.json``: the cell's
configuration ``bench/configs/<config>.json``, its traffic mix
``bench/traffic/<traffic>.json`` (whose ``generator`` names
``bench/generators/<generator>.py``), the limits of its compared numbers
``bench/limits/<cell>.json`` and each per-layer metric's reader
``bench/metrics/<metric>.py``.

A generator module provides ``prepare(ctx)`` (set-up: build the inputs
from the seed and warm every program shape the window uses),
``window(ctx, state)`` (the measured work; returns ``attempted``,
``failed``, ``end_to_end`` values and ``counters``) and ``readings(ctx,
state, window, control=False)`` (the numbers compared with the limits).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Optional

from lib import trace as trace_lib
from lib.spans import Spans

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class NoDevice(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Ctx:
    cell: dict
    config: dict
    mix: dict
    seed: int
    seconds: float
    chips: int
    spans: Spans


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def for_cell(entries, cell: str):
    """The entries of ``end_to_end`` or ``per_layer`` this cell reports."""
    return [m for m in entries if cell in m.get("workloads", [cell])]


class CompileCounter:
    """JAX monitoring listener counting tracing and compilation events
    while ``on`` is set."""

    def __init__(self):
        self.on = False
        self.counts = {}
        self._lock = threading.Lock()

    def __call__(self, event: str, duration: float, **_) -> None:
        if self.on and event.startswith("/jax/core/compile/"):
            with self._lock:
                self.counts[event] = self.counts.get(event, 0) + 1


def device_block(chips: int, require: bool) -> dict:
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if require and d0.platform != "tpu":
        raise NoDevice(f"device 0 is {d0.platform!r}, not a TPU")
    if require and len(devs) < chips:
        raise NoDevice(f"{len(devs)} chips, the cell needs {chips}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def load_cell(cell_name: str, config: Optional[dict] = None,
              limits: Optional[dict] = None):
    """``(benchmark, cell, config, mix, limits, generator)`` of a cell;
    ``config`` and ``limits`` replace the files of that name
    (tests use it to run at a small size)."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise SystemExit(f"unknown workload {cell_name!r}")
    cell = cells[cell_name]
    config = config or load_json(BENCH / "configs" / f"{cell['config']}.json")
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = limits or load_json(BENCH / "limits" / f"{cell_name}.json")
    gen = load_module(BENCH / "generators" / f"{mix['generator']}.py")
    return bench, cell, config, mix, limits, gen


def init_jax(chips: int, require_device: bool):
    """Check the device, turn the program's compile cache on (see
    ``repro.launch.compile_cache``) for every program, however small, and
    count compile events; returns ``(device block, counter)``."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    device = device_block(chips, require_device)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    return device, compiles


def run_cell(cell_name: str, seed: int, seconds: float, traced: bool,
             t_start: float, *, require_device: bool = True,
             config: Optional[dict] = None,
             limits: Optional[dict] = None) -> dict:
    """Run one cell and return the result line's object (see
    :func:`load_cell` for ``config`` and ``limits``)."""
    bench, cell, config, mix, limits, gen = load_cell(cell_name, config,
                                                      limits)
    device, compiles = init_jax(cell["chips"], require_device)
    spans = Spans(traced)
    ctx = Ctx(cell=cell, config=config, mix=mix, seed=seed, seconds=seconds,
              chips=cell["chips"], spans=spans)
    state = gen.prepare(ctx)
    setup_s = time.perf_counter() - t_start

    tmp = tempfile.TemporaryDirectory() if traced else None
    compiles.on = True
    try:
        if traced:
            spans.start(tmp.name)
        win = gen.window(ctx, state)
    finally:
        spans.stop()
        compiles.on = False
        spans.close()
    device["memory_peak_bytes"] = memory_peak(cell["chips"])
    print(f"[harness] compile events in the window: "
          f"{sum(compiles.counts.values())} {compiles.counts}",
          file=sys.stderr, flush=True)

    metrics, breakdown = {}, None
    if traced:
        path = next(Path(tmp.name).rglob("*.xplane.pb"))
        t0 = time.perf_counter()
        reduced = trace_lib.reduce(str(path))
        print(f"[harness] trace {path.stat().st_size} bytes reduced in "
              f"{time.perf_counter() - t0:.3f} s; host spans (s, n): "
              f"{[(k, reduced.span_s[k], reduced.span_n[k]) for k in sorted(reduced.span_s)]}",
              file=sys.stderr)
        tmp.cleanup()
        device["busy_s"] = reduced.mean_busy_s
        device["window_s"] = reduced.window_s
        breakdown = {"device_ops": trace_lib.top(reduced.op_s),
                     "idle_gaps": trace_lib.top(reduced.idle_by_span)}
        view = {"counters": win["counters"], "trace": reduced,
                "spans": spans.counts}
        for m in for_cell(bench["per_layer"], cell_name):
            value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(
                view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in for_cell(bench["end_to_end"], cell_name):
            value = (setup_s if m["name"] == "setup_s"
                     else win["end_to_end"].get(m["name"]))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    numbers = gen.readings(ctx, state, win)
    checks = {}
    for name, value in numbers.items():
        limit = limits.get(name, {}).get("limit")
        checks[name] = {"value": value, "limit": limit}
    correct = (win["attempted"] > 0 and win["failed"] == 0
               and all(c["limit"] is not None and c["value"] <= c["limit"]
                       for c in checks.values()))
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"check attempted {win['attempted']} failed {win['failed']} "
          f"correct {correct}", file=sys.stderr, flush=True)
    out = {"correct": correct, "attempted": win["attempted"],
           "failed": win["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
