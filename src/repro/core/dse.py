"""Flexibility-aware Design-Space Exploration (paper Fig 6).

Toolflow: (DNN model description, baseline HW resources, HW flexibility
specification) -> selects the map space -> internal MSE (GA) -> best design
point + HW performance (runtime, energy, area, power).

Also implements the Sec 7 "future-proofing" workflow:
  1. design InFlex-0000-<model>-Opt: one TOPS(R) config optimized for a
     model (the representation axis is frozen to the searched bit-width),
  2. derive flexible variants that keep the frozen config on inflexible axes
     but open chosen axes (FullFlex/PartFlex-xxxxx-<model>-Opt; 4-char class
     strings keep the paper's T/O/P/S sweep with R pinned),
  3. replay all variants on "future" models.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import area_model, tracing
from .flexion import FlexionReport
from .flexion_batched import flexion_campaign, model_flexion_campaign
from .mapper import (GAConfig, ModelResult, evaluate_fixed_genome,
                     evaluate_fixed_genome_many, search_campaign,
                     search_fixed_config, search_fixed_configs)
from .mapspace import MapSpace
from .spec import (FULLFLEX, INFLEX, PARTFLEX, FlexSpec, HWConfig, OrderSpec,
                   ParallelSpec, RepresentationSpec, ShapeSpec, TileSpec,
                   perm_to_order_str)
from .workloads import DIMS, Layer, get_model


@dataclasses.dataclass
class DSEResult:
    spec_name: str
    class_str: str
    runtime: float
    energy: float
    edp: float
    area: float
    power: float
    flexion: Optional[FlexionReport]
    model_result: ModelResult

    def row(self) -> Dict[str, float]:
        return dict(name=self.spec_name, cls=self.class_str,
                    runtime=self.runtime, energy=self.energy, edp=self.edp,
                    area=self.area, power=self.power,
                    hf=self.flexion.hf if self.flexion else float("nan"),
                    wf=self.flexion.wf if self.flexion else float("nan"))


def run_dse(layers: Sequence[Layer], candidates: Sequence[FlexSpec],
            cfg: Optional[GAConfig] = None, with_flexion: bool = False,
            flexion_samples: int = 20_000) -> List[DSEResult]:
    """Evaluate candidate accelerators; every DSE step includes a full MSE
    per benchmark layer (paper Sec 2.4).

    Candidates sharing an HWConfig are searched as ONE campaign row set
    (rows = specs x unique layers; the engine takes one HWConfig per call);
    results come back in candidate order and are bit-identical to per-spec
    ``search_model`` calls.  ``with_flexion`` likewise estimates every
    candidate's flexion through one ``model_flexion_campaign`` batch
    (bit-identical to per-spec ``model_flexion`` calls, with the C_X
    reference sampled once per HWConfig)."""
    cfg = cfg or GAConfig()
    candidates = list(candidates)
    if not candidates:
        return []      # an empty candidate set is a valid (empty) DSE
    by_hw: Dict[HWConfig, List[int]] = {}
    for i, spec in enumerate(candidates):
        by_hw.setdefault(spec.hw, []).append(i)
    mres_list: List[Optional[ModelResult]] = [None] * len(candidates)
    for idx in by_hw.values():
        found = search_campaign([(layers, candidates[i]) for i in idx], cfg)
        for i, mres in zip(idx, found):
            mres_list[i] = mres
    if with_flexion:
        flex_list = model_flexion_campaign(
            [(spec, layers) for spec in candidates], flexion_samples)
    else:
        flex_list = [None] * len(candidates)
    out = []
    for spec, mres, flexion in zip(candidates, mres_list, flex_list):
        ar = area_model.area_of(spec)
        out.append(DSEResult(
            spec_name=spec.name, class_str=spec.class_str(),
            runtime=mres.runtime, energy=mres.energy, edp=mres.edp,
            area=ar.total_area, power=ar.total_power, flexion=flexion,
            model_result=mres))
    return out


# --------------------------------------------------------------------------
# Sec 7: future-proofing workflow
# --------------------------------------------------------------------------

def design_fixed_accelerator(model_name: str, hw: Optional[HWConfig] = None,
                             cfg: Optional[GAConfig] = None
                             ) -> Tuple[FlexSpec, np.ndarray, ModelResult]:
    """InFlex-0000-<model>-Opt: harden the best single mapping into silicon."""
    hw = hw or HWConfig()
    layers = get_model(model_name)
    # search over the full space for the best *single* config
    probe_spec = FlexSpec(name=f"probe-{model_name}", hw=hw)
    genome, res = search_fixed_config(layers, probe_spec, cfg)
    spec = freeze_spec_from_genome(probe_spec, layers, genome,
                                   name=f"InFlex0000-{model_name}-Opt")
    return spec, genome, res


def freeze_spec_from_genome(probe_spec: FlexSpec, layers: Sequence[Layer],
                            genome: np.ndarray, name: str) -> FlexSpec:
    """Turn a search genome into an InFlex-00000 spec (fixed T/O/P/S/R)."""
    probe = Layer("probe", tuple(int(v) for v in
                                 np.max([l.dims for l in layers], axis=0)))
    space = MapSpace(probe, probe_spec)
    m = space.decode(space.clip(genome[None, :])[0])
    return FlexSpec(
        name=name, hw=probe_spec.hw,
        tile=TileSpec(flex=INFLEX, fixed_tile=m.tiles),
        order=OrderSpec(flex=INFLEX, fixed_order=perm_to_order_str(m.order)),
        parallel=ParallelSpec(flex=INFLEX,
                              fixed_pair=(DIMS[m.parallel[0]],
                                          DIMS[m.parallel[1]])),
        shape=ShapeSpec(flex=INFLEX, fixed_shape=m.shape),
        representation=RepresentationSpec(flex=INFLEX,
                                          fixed_bits=int(m.repr_bits)),
    )


def open_axes(frozen: FlexSpec, class_str: str, level: str = FULLFLEX,
              name: Optional[str] = None) -> FlexSpec:
    """Open the axes marked '1' in class_str on an otherwise frozen design
    (FullFlex-xxxx-<model>-Opt in Fig 13).  4-char class strings keep the
    paper's T/O/P/S sweep (R stays pinned); 5-char strings also open the
    representation axis (FullFlex-xxxx1 ... the 2^5 future-proofing sweep)."""
    assert len(class_str) in (4, 5)
    t, o, p, s, r = class_str.ljust(5, "0")
    prefix = {PARTFLEX: "PartFlex", FULLFLEX: "FullFlex"}[level]
    return FlexSpec(
        name=name or f"{prefix}{class_str}-" + frozen.name.split("-", 1)[-1],
        hw=frozen.hw,
        tile=dataclasses.replace(frozen.tile,
                                 flex=level if t == "1" else INFLEX),
        order=dataclasses.replace(frozen.order,
                                  flex=level if o == "1" else INFLEX),
        parallel=dataclasses.replace(frozen.parallel,
                                     flex=level if p == "1" else INFLEX),
        shape=dataclasses.replace(frozen.shape,
                                  flex=level if s == "1" else INFLEX),
        representation=dataclasses.replace(
            frozen.representation, flex=level if r == "1" else INFLEX),
    )


def future_proofing_study(base_model: str = "alexnet",
                          future_models: Sequence[str] = (
                              "alexnet", "mnasnet", "resnet50", "mobilenetv2",
                              "bert", "dlrm", "ncf"),
                          class_strs: Sequence[str] = (
                              "1000", "0100", "0010", "0001", "0011", "0101",
                              "1001", "0110", "1010", "1100", "1110", "1011",
                              "0111", "1101", "1111"),
                          hw: Optional[HWConfig] = None,
                          cfg: Optional[GAConfig] = None,
                          include_partflex_1111: bool = True,
                          campaign: bool = False,
                          timings: Optional[Dict[str, float]] = None,
                          flexion: Optional[Dict[str, float]] = None,
                          wflexion: Optional[Dict[str, float]] = None,
                          flexion_samples: int = 20_000,
                          results: Optional[Dict[Tuple[str, str],
                                                 Tuple[FlexSpec,
                                                       ModelResult]]] = None
                          ) -> Dict[str, Dict[str, float]]:
    """Fig 13: rows = accelerator variants, cols = models, values = runtime
    normalized to InFlex-0000-<base>-Opt on that model.

    ``campaign=True`` batches each of the three phases across *every* model
    instead of looping model-by-model: one ``search_fixed_configs`` call
    designs all InFlex-0000-X-Opt accelerators (one stacked genome tensor
    per shape bucket), one ``evaluate_fixed_genome_many`` pass replays the
    frozen design everywhere, and one ``search_campaign`` row set sweeps all
    (model, variant) MSEs through the engine — chunk-pipelined when
    ``cfg.pipeline`` is set.  The table is bit-identical either way; only
    batching and wall clock change.

    ``timings`` (optional dict) accumulates per-phase seconds
    (``time.perf_counter``) under ``design_fixed`` / ``replay_frozen`` /
    ``flex_sweep`` (and ``flexion`` when requested) — the BENCH artifact's
    phase breakdown.  The study runs inside ``tracing.recording(timings)``,
    so the dict also receives every span and counter of the layers below
    (``study.*``, ``engine.*``, ``flexion.*``, ``design.*``, ``jax:*``; see
    :mod:`repro.core.tracing`).

    ``flexion`` (optional dict) adds the H-F column: it is filled with
    ``{row_name: hf}`` for every table row, estimated through one
    ``flexion_campaign`` batch over all accelerator variants (the
    ``InFlex0000-X-Opt`` family shares the frozen design's value — H-F is
    workload-agnostic, so every InFlex-0000 spec on the same HW resources
    scores identically).

    ``wflexion`` (optional dict) likewise adds the W-F column:
    ``{row_name: wf}`` per table row, estimated through one
    ``model_flexion_campaign`` batch where each variant spec is paired with
    the union of every future model's layers (W-F is workload-dependent, so
    the column reports the variant's average coverage of the whole future
    suite's map spaces).

    ``results`` (optional dict) receives ``{(row_name, model): (spec,
    ModelResult)}`` for every table cell before normalization: the
    searched or replayed per-layer mappings and costs, and the spec they
    were evaluated under."""
    cfg = cfg or GAConfig()
    cells: Dict[Tuple[str, str], Tuple[FlexSpec, ModelResult]] = \
        results if results is not None else {}
    t_acc: Dict[str, float] = timings if timings is not None else {}

    @contextlib.contextmanager
    def phase(name: str):
        with tracing.span("study." + name) as s:
            yield
        t_acc[name] = round(t_acc.get(name, 0.0) + s.seconds, 6)

    with tracing.recording(t_acc):
        designs: Dict[str, Tuple[np.ndarray, ModelResult]] = {}
        with phase("design_fixed"):
            if campaign:
                hw_ = hw or HWConfig()
                names = list(dict.fromkeys([base_model, *future_models]))
                designs = dict(zip(names, search_fixed_configs(
                    [(get_model(m), FlexSpec(name=f"probe-{m}", hw=hw_))
                     for m in names], cfg)))
                genome, _ = designs[base_model]
                frozen = freeze_spec_from_genome(
                    FlexSpec(name=f"probe-{base_model}", hw=hw_),
                    get_model(base_model), genome,
                    name=f"InFlex0000-{base_model}-Opt")
            else:
                frozen, genome, _ = design_fixed_accelerator(base_model, hw,
                                                             cfg)

        table: Dict[str, Dict[str, float]] = {}
        baseline_rt: Dict[str, float] = {}

        # row 1: the frozen 2014 accelerator on every model
        with phase("replay_frozen"):
            if campaign:
                replays = evaluate_fixed_genome_many(
                    [(get_model(m), frozen, genome) for m in future_models])
            else:
                replays = [evaluate_fixed_genome(get_model(m), frozen, genome)
                           for m in future_models]
            row = {m: res.runtime for m, res in zip(future_models, replays)}
            cells.update({(frozen.name, m): (frozen, res)
                          for m, res in zip(future_models, replays)})
            baseline_rt.update(row)
            table[f"InFlex0000-{base_model}-Opt"] = row

        # row 2: a fixed accelerator re-optimized per future model (already
        # designed above in campaign mode)
        with phase("design_fixed"):
            row = {}
            for m in future_models:
                if m == base_model:
                    cells["InFlex0000-X-Opt", m] = cells[frozen.name, m]
                elif campaign:
                    cells["InFlex0000-X-Opt", m] = (
                        FlexSpec(name=f"probe-{m}", hw=frozen.hw),
                        designs[m][1])
                else:
                    spec_m, _, res = design_fixed_accelerator(m, hw, cfg)
                    cells["InFlex0000-X-Opt", m] = (
                        FlexSpec(name=f"probe-{m}", hw=spec_m.hw), res)
                row[m] = cells["InFlex0000-X-Opt", m][1].runtime
            table["InFlex0000-X-Opt"] = row

        # flexible variants of the 2014 design: each model's whole spec
        # sweep is a few chunked engine dispatches — and the campaign packs
        # ALL models' sweeps into one chunk-pipelined row set
        flex_specs = [open_axes(frozen, cs, FULLFLEX) for cs in class_strs]
        if include_partflex_1111:
            flex_specs.append(open_axes(frozen, "1111", PARTFLEX))

        if flexion is not None or wflexion is not None:
            with phase("flexion"):
                fx_specs = [frozen, *flex_specs]
                if flexion is not None:
                    reports = flexion_campaign(
                        [(s, None, 0) for s in fx_specs],
                        mc_samples=flexion_samples, seed=0)
                    flexion.update({s.name: r.hf
                                    for s, r in zip(fx_specs, reports)})
                    flexion["InFlex0000-X-Opt"] = flexion[frozen.name]
                if wflexion is not None:
                    future_layers = [l for m in future_models
                                     for l in get_model(m)]
                    wreports = model_flexion_campaign(
                        [(s, future_layers) for s in fx_specs],
                        flexion_samples)
                    wflexion.update(
                        {s.name: r.wf for s, r in zip(fx_specs, wreports)})
                    wflexion["InFlex0000-X-Opt"] = wflexion[frozen.name]
        for spec in flex_specs:
            table[spec.name] = {}
        with phase("flex_sweep"):
            if campaign:
                all_res = iter(search_campaign(
                    [(get_model(m), spec) for m in future_models
                     for spec in flex_specs], cfg))
                for m in future_models:
                    for spec in flex_specs:
                        cells[spec.name, m] = (spec, next(all_res))
            else:
                for m in future_models:
                    layers = get_model(m)
                    model_res = search_campaign(
                        [(layers, spec) for spec in flex_specs], cfg)
                    for spec, mres in zip(flex_specs, model_res):
                        cells[spec.name, m] = (spec, mres)
            for m in future_models:
                for spec in flex_specs:
                    table[spec.name][m] = cells[spec.name, m][1].runtime

    # normalize by the frozen baseline per column
    base_row = table[f"InFlex0000-{base_model}-Opt"]
    norm = {r: {m: v / base_row[m] for m, v in cols.items()}
            for r, cols in table.items()}
    return norm


def geomean_speedup(norm_table: Dict[str, Dict[str, float]],
                    flex_row: str, models: Optional[Sequence[str]] = None
                    ) -> float:
    """Geomean of 1/normalized-runtime for a flexible row (paper: 11.8x)."""
    row = norm_table[flex_row]
    models = models or list(row.keys())
    vals = np.asarray([row[m] for m in models], np.float64)
    return float(np.exp(np.mean(np.log(1.0 / np.maximum(vals, 1e-12)))))
