"""Shared benchmark helpers: budgets, layer lookup, CSV emission."""
from __future__ import annotations

import time
from typing import Dict, List, Optional

from repro.core import GAConfig, Layer, get_model
from repro.core.envvars import get_env

# Budgets: FAST (tests / CI smoke), DEFAULT (bench runs), FULL (paper 100x100)
BUDGETS = {
    "fast": GAConfig(population=24, generations=10),
    "default": GAConfig(population=48, generations=30),
    "full": GAConfig(population=100, generations=100),
}


def bench_mode() -> str:
    """Current REPRO_BENCH_MODE — read lazily (per call, not at import) so
    tests and multi-pass runners can flip the env between runs."""
    return get_env("REPRO_BENCH_MODE", "default")


def ga_budget(scale: float = 1.0) -> GAConfig:
    """The GA budget for the current REPRO_BENCH_MODE, with chunk pipelining
    on (host draw prep overlapped with device compute) — the campaign path
    every bench runs."""
    import dataclasses
    base = dataclasses.replace(BUDGETS[bench_mode()], pipeline=True)
    if scale != 1.0:
        base = dataclasses.replace(
            base, generations=max(4, int(base.generations * scale)))
    return base


def flexion_reports(pairs, mc_samples: int,
                    timings: Optional[Dict[str, float]] = None,
                    phase: str = "flexion"):
    """Flexion reports for ``(spec, layer)`` pairs, in input order, from one
    batched ``flexion_campaign`` call (every row uses seed 0, the
    single-call default).  Starts cache-cold so the recorded phase timing
    does not depend on what ran before it.
    """
    from repro.core import clear_flexion_reference_cache, flexion_campaign
    clear_flexion_reference_cache()
    t0 = time.time()
    reports = flexion_campaign([(spec, layer, 0) for spec, layer in pairs],
                               mc_samples=mc_samples, seed=0)
    if timings is not None:
        timings[phase] = round(time.time() - t0, 6)
    return reports


def find_layer(model: str, dims) -> Layer:
    """Locate a layer by its exact (K,C,Y,X,R,S) tuple (the paper quotes
    layers by dims, e.g. MnasNet Layer-29 = (1,480,14,14,5,5))."""
    for layer in get_model(model):
        if tuple(layer.dims) == tuple(dims):
            return layer
    raise KeyError(f"{dims} not in {model}")


# the paper's quoted MnasNet layers
MNASNET_LAYERS = {
    "layer1": (32, 3, 224, 224, 3, 3),
    "layer10": (72, 24, 56, 56, 1, 1),
    "layer16": (120, 40, 28, 28, 1, 1),
    "layer29": (1, 480, 14, 14, 5, 5),
}


class Table:
    """Collects rows, prints aligned, returns derived metrics."""

    def __init__(self, title: str, columns: List[str]):
        self.title = title
        self.columns = columns
        self.rows: List[List] = []

    def add(self, *row):
        self.rows.append(list(row))

    def show(self, print_fn=print):
        print_fn(f"\n== {self.title} ==")
        widths = [max(len(str(c)), *(len(_fmt(r[i])) for r in self.rows))
                  if self.rows else len(str(c))
                  for i, c in enumerate(self.columns)]
        print_fn("  ".join(str(c).ljust(w)
                           for c, w in zip(self.columns, widths)))
        for r in self.rows:
            print_fn("  ".join(_fmt(v).ljust(w)
                               for v, w in zip(r, widths)))


def _fmt(v) -> str:
    if isinstance(v, float):
        if v == 0:
            return "0"
        if abs(v) >= 1000 or abs(v) < 0.001:
            return f"{v:.3g}"
        return f"{v:.3f}"
    return str(v)


def timed(fn):
    t0 = time.time()
    out = fn()
    return out, time.time() - t0
