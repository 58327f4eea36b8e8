"""The reference GA the engine is held to, bit for bit.

``search_layer`` is the plain per-layer genetic algorithm: a Python loop
over generations, one ``evaluate_population`` dispatch each, breeding on the
host through ``ga_ops.next_population``.  It draws the same per-row random
streams (``ga_ops.draw_run``) and applies the same operator arithmetic as
the one-program engine (``repro.core.engine``), so for the same layer, spec
and ``GAConfig`` both return identical results.

``run_rows`` has ``run_batched_ga``'s signature, so a test can monkeypatch
``repro.core.mapper.run_batched_ga`` with it and run ``search``,
``search_model`` and ``search_campaign`` (and every bench built on them) on
the reference instead of the engine.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import ga_ops
from repro.core.cost_model import CostResult, evaluate_population
from repro.core.engine import EngineRow, RowResult
from repro.core.mapper import GAConfig, MapperResult, _kind_args
from repro.core.mapspace import mapspace_for
from repro.core.spec import FlexSpec
from repro.core.workloads import Layer


def _objective_values(res: CostResult, objective: str) -> np.ndarray:
    arr = {"runtime": res.runtime, "energy": res.energy,
           "edp": res.edp}[objective]
    return np.asarray(arr)


def _search(layer: Layer, spec: FlexSpec, cfg: GAConfig
            ) -> Tuple[np.ndarray, MapperResult]:
    """Per-layer GA with one device dispatch per generation; returns the
    best genome and its result."""
    rng = np.random.default_rng(cfg.seed)
    space = mapspace_for(layer, spec)
    pop = ga_ops.initial_population(rng, space, cfg)
    n_elite = ga_ops.n_elite(cfg)
    draws = ga_ops.draw_run(rng, space, cfg, cfg.generations,
                            cfg.population - n_elite)
    lens = space.table_lens()

    dims = jnp.asarray(layer.dims)
    stride = jnp.asarray(layer.stride)
    dw = jnp.asarray(layer.depthwise)
    # native-pinned R runs the pre-R cost program (bit parity with v4)
    r_live = (len(space.repr_table) > 1
              or int(space.repr_table[0]) != 8 * spec.hw.bytes_per_elem)
    grouped, groups = _kind_args([layer])
    grouped = None if grouped is None else grouped[0]
    groups = None if groups is None else (groups[0][0], groups[1][0])

    best_hist: List[float] = []
    best_g: Optional[np.ndarray] = None
    best_obj = np.inf
    best_idx_res: Optional[Tuple[CostResult, int]] = None

    for gen in range(cfg.generations):
        tiles, orders, pairs, shapes, reprs = space.decode_batch(pop)
        res = evaluate_population(
            dims, stride, dw, jnp.asarray(tiles), jnp.asarray(orders),
            jnp.asarray(pairs), jnp.asarray(shapes), spec.hw,
            space.hard_partition,
            jnp.asarray(reprs) if r_live else None, grouped, groups)
        obj = _objective_values(res, cfg.objective)
        order_idx = np.argsort(obj, kind="stable")
        if obj[order_idx[0]] < best_obj:
            best_obj = float(obj[order_idx[0]])
            best_g = pop[order_idx[0]].copy()
            best_idx_res = (res, int(order_idx[0]))
        best_hist.append(best_obj)

        pop = ga_ops.next_population(pop, order_idx,
                                     ga_ops.gen_slice(draws, gen),
                                     space.tile_lo, space.tile_hi, lens,
                                     n_elite, np)

    assert best_g is not None and best_idx_res is not None
    res, i = best_idx_res
    return best_g, MapperResult(
        mapping=space.decode(best_g),
        runtime=float(res.runtime[i]), energy=float(res.energy[i]),
        edp=float(res.edp[i]), util=float(res.util[i]),
        dram_elems=float(res.dram_elems[i]),
        feasible=bool(res.feasible[i]), history=best_hist,
    )


def search_layer(layer: Layer, spec: FlexSpec, cfg: GAConfig
                 ) -> MapperResult:
    """The reference MSE of one layer on one accelerator."""
    return _search(layer, spec, cfg)[1]


def run_rows(rows: Sequence[EngineRow], cfg: GAConfig,
             row_cache=None) -> List[RowResult]:
    """Every row through the reference GA with the row's seed, returned as
    the engine returns it.  ``row_cache`` is accepted and unused: a cache
    changes no result."""
    out = []
    for row in rows:
        best_g, r = _search(row.layer, row.spec,
                            dataclasses.replace(cfg, seed=row.seed))
        out.append(RowResult(
            best_genome=best_g, best_obj=r.history[-1], history=r.history,
            runtime=r.runtime, energy=r.energy, edp=r.edp, util=r.util,
            dram_elems=r.dram_elems, feasible=r.feasible))
    return out
