"""GAMMA-style genetic-algorithm mapper with flexibility-constrained operators
(paper Sec 5).

The native GAMMA mapper supports InFlex-0000 or FullFlex-1111; the paper's
extension (reproduced here) constrains the search inside any of the 16
classes and further inside PartFlex subsets:

  * inflexible axes are *pinned* (genes never mutate off the fixed value),
  * PartFlex axes index into restricted tables (orders / pairs / shapes) or
    apply the hard-partition legality (tiles),
  * FullFlex axes roam the full constrained space C_X.

Every map-space search runs on one engine (repro.core.engine): the GA of
every unique (layer, spec) row is stacked into an (L, P, 10) genome tensor
and runs as ONE jitted XLA program per chunk of rows.  ``search``,
``search_model`` and ``search_campaign`` only plan rows and fold the row
results back.  The tests hold the engine bit for bit to a plain per-layer
reference GA (tests/_reference_ga.py) that consumes the same random streams
and operator arithmetic (repro.core.ga_ops).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.pool import InFlightQueue, parse_device_spec

from . import device_pool, ga_ops, tracing
from .cost_model import CostResult, evaluate_kinds_impl, evaluate_rows
from .engine import (ROW_BUCKET, EngineRow, _bucket, pack_chunks,
                     run_batched_ga)
from .mapspace import Mapping, MapSpace, mapspace_for
from .spec import FlexSpec
from .workloads import Layer, NUM_DIMS, group_table, layers_as_array


def _normalize_devices(devices):
    """Canonicalize ``GAConfig.devices`` to a hashable form (int count,
    index tuple, or stripped string) and *validate it at construction*
    through the one grammar in ``repro.dist.pool.parse_device_spec`` — a
    bad spec fails here with a clear ValueError instead of deep inside a
    chunk dispatch, and GAConfig can never accept a spec the env var / CLI
    forms would reject."""
    if isinstance(devices, np.integer):
        devices = int(devices)
    if isinstance(devices, str):
        devices = devices.strip()
        if not devices:
            return None
    elif not isinstance(devices, int):      # bools flow through to parse
        try:
            devices = tuple(int(i) for i in devices)
        except TypeError as e:
            raise ValueError(f"invalid devices spec {devices!r}") from e
    parse_device_spec(devices)              # raises ValueError on garbage
    return devices


@dataclasses.dataclass(frozen=True)
class GAConfig:
    population: int = 100
    generations: int = 100      # paper: 100x100 = 10K samples
    elite_frac: float = 0.10
    mutation_rate: float = 0.5  # paper: 0.5
    crossover_rate: float = 0.5
    tile_divisor_bias: float = 0.3  # GAMMA-style: snap tiles to divisors
    seed: int = 0
    objective: str = "runtime"  # runtime | energy | edp
    pipeline: bool = False      # overlap host draw prep with device compute
                                # across engine chunks (scheduling only —
                                # results are bit-identical either way)
    devices: Optional[object] = None
                                # device pool for engine/replay chunks: a
                                # count, "all", or tuple of local-device
                                # indices (None -> REPRO_DEVICES env ->
                                # default placement); placement only, so
                                # results are bit-identical either way

    def __post_init__(self):
        # Degenerate GA shapes (generations=0 returns an inf-objective
        # garbage row; elite_frac >= 1 or population < 2 leave no children
        # to breed) are rejected HERE, at construction, with an actionable
        # message.
        if self.population < 2:
            raise ValueError(
                f"population must be >= 2 (elites plus at least one child), "
                f"got {self.population}")
        if self.generations < 1:
            raise ValueError(
                f"generations must be >= 1, got {self.generations}")
        if not 0.0 <= self.elite_frac < 1.0:
            raise ValueError(
                f"elite_frac must be in [0, 1) so n_children >= 1, "
                f"got {self.elite_frac}")
        for field in ("mutation_rate", "crossover_rate"):
            v = getattr(self, field)
            if not 0.0 <= v <= 1.0:
                raise ValueError(
                    f"{field} must be in [0, 1], got {v}")
        if self.objective not in ("runtime", "energy", "edp"):
            raise ValueError(f"unknown objective {self.objective!r}")
        if self.devices is not None:
            object.__setattr__(self, "devices",
                               _normalize_devices(self.devices))


@dataclasses.dataclass
class MapperResult:
    mapping: Mapping
    runtime: float
    energy: float
    edp: float
    util: float
    dram_elems: float
    feasible: bool
    history: List[float]        # best objective per generation

    def objective(self, name: str) -> float:
        return {"runtime": self.runtime, "energy": self.energy,
                "edp": self.edp}[name]


class _Operators:
    """Constraint-respecting GA operators over genome matrices (N, 10).

    Thin host-side wrapper over the shared draw/apply functions in
    ``ga_ops`` (the engine applies the identical arithmetic in JAX); the
    fixed-config search breeds with it."""

    def __init__(self, space: MapSpace, cfg: GAConfig,
                 rng: np.random.Generator):
        self.space = space
        self.cfg = cfg
        self.rng = rng

    def mutate(self, g: np.ndarray) -> np.ndarray:
        d = ga_ops.single_generation_draws(self.rng, self.space, self.cfg,
                                           len(g))
        return ga_ops.apply_mutation(np.asarray(g), d, self.space.tile_lo,
                                     self.space.tile_hi,
                                     self.space.table_lens(), np)

    def crossover(self, parents: np.ndarray) -> np.ndarray:
        d = ga_ops.single_generation_draws(self.rng, self.space, self.cfg,
                                           len(parents))
        return self.space.clip(
            ga_ops.apply_crossover(np.asarray(parents), d, np))


def _row_to_result(layer: Layer, spec: FlexSpec, row) -> MapperResult:
    space = mapspace_for(layer, spec)
    return MapperResult(
        mapping=space.decode(row.best_genome),
        runtime=row.runtime, energy=row.energy, edp=row.edp,
        util=row.util, dram_elems=row.dram_elems, feasible=row.feasible,
        history=row.history,
    )


def search(layer: Layer, spec: FlexSpec,
           cfg: Optional[GAConfig] = None) -> MapperResult:
    """MSE for one layer on one accelerator (paper Fig 6 inner loop)."""
    cfg = cfg or GAConfig()
    row = run_batched_ga([EngineRow(layer, spec, cfg.seed)], cfg)[0]
    return _row_to_result(layer, spec, row)


@dataclasses.dataclass
class ModelResult:
    per_layer: List[MapperResult]
    runtime: float
    energy: float
    edp: float

    @property
    def feasible(self) -> bool:
        return all(r.feasible for r in self.per_layer)


def _dedup_key(layer: Layer) -> tuple:
    """The spec-relevant layer fields — exactly what the cost model reads:
    dims, stride, kind and a ragged layer's group rows.  Layer *names* (and
    any future metadata) must never enter this key."""
    return (layer.dims, layer.stride, layer.depthwise, layer.kind,
            layer.group_rows)


def _kind_args(layers: Sequence[Layer], n_rows: Optional[int] = None):
    """``(grouped, groups)`` for a batch of ``layers`` padded to ``n_rows``
    rows: the grouped flags, or None when no layer is grouped, and the
    ``(group_dims, group_live)`` table of the ragged program variant, or
    None when no layer is ragged — None keeps the program the plain kinds
    ran before."""
    n_rows = len(layers) if n_rows is None else n_rows
    grouped = None
    if any(l.grouped for l in layers):
        grouped = np.zeros(n_rows, np.bool_)
        grouped[:len(layers)] = [l.grouped for l in layers]
    groups = None
    if any(l.ragged for l in layers):
        groups = group_table(layers, n_rows)
    return grouped, groups


def plan_model_rows(layers: Sequence[Layer], dedup: bool = True
                    ) -> Tuple[List[int], Dict[tuple, int]]:
    """One model's engine-row plan: ``row_index`` lists the first-occurrence
    layer indices that become rows, ``seen`` maps each dedup key to its row
    position.  THE row-planning convention — ``search_model``,
    ``search_campaign`` and the DSE service all call this one function, so
    their per-layer GA seeds (``cfg.seed + 1000 * first_occurrence_index``)
    and dedup behavior can never drift apart."""
    row_index: List[int] = []
    seen: Dict[tuple, int] = {}
    for i, layer in enumerate(layers):
        key = _dedup_key(layer)
        if dedup and key in seen:
            continue
        seen[key] = len(row_index)
        row_index.append(i)
    return row_index, seen


def request_rows(layers: Sequence[Layer], spec: FlexSpec, cfg: "GAConfig",
                 row_index: Sequence[int]) -> List[EngineRow]:
    """The planned rows as :class:`EngineRow`\\ s with the campaign seed
    convention (``cfg.seed + 1000 * first_occurrence_index``)."""
    return [EngineRow(layers[i], spec, cfg.seed + 1000 * i)
            for i in row_index]


def assemble_model_result(layers: Sequence[Layer], spec: FlexSpec,
                          row_index: Sequence[int], seen: Dict[tuple, int],
                          row_results: Sequence, dedup: bool = True
                          ) -> ModelResult:
    """Fold one request's engine-row results back into a :class:`ModelResult`
    (the inverse of :func:`plan_model_rows`); deduped layers share their
    first occurrence's MapperResult object."""
    per_row = [_row_to_result(layers[i], spec, r)
               for i, r in zip(row_index, row_results)]
    if dedup:
        results = [per_row[seen[_dedup_key(l)]] for l in layers]
    else:
        results = list(per_row)
    return _model_result(results)


def _model_result(results: Sequence[MapperResult]) -> ModelResult:
    runtime = float(sum(r.runtime for r in results))
    energy = float(sum(r.energy for r in results))
    return ModelResult(per_layer=list(results), runtime=runtime,
                       energy=energy, edp=runtime * energy)


def search_model(layers: Sequence[Layer], spec: FlexSpec,
                 cfg: Optional[GAConfig] = None,
                 dedup: bool = True,
                 row_cache=None) -> ModelResult:
    """Per-layer MSE (flexible accelerators re-map every layer; paper Sec 3.1
    scope: layers run sequentially).  All unique layers' GAs run in ONE
    jitted XLA program (an (L, P, 10) genome tensor through a fori_loop over
    generations) — see repro.core.engine.

    Dedup cache: identical layer *shapes* share one search — ResNet-style
    nets repeat blocks heavily.  The cache key is :func:`_dedup_key`, i.e.
    only the spec-relevant fields (dims, stride, kind); layer names
    are deliberately excluded, so two differently-named layers with equal
    shapes resolve to the same (shared) MapperResult object.  Per-layer GA
    seeds derive from the *first occurrence* index (``seed + 1000*i``), so
    dedup changes no result, only how often the search runs.
    ``row_cache`` answers already-searched rows from a persistent store (see
    :func:`repro.core.engine.run_batched_ga`) without changing any result.
    """
    cfg = cfg or GAConfig()
    row_index, seen = plan_model_rows(layers, dedup)
    rows = request_rows(layers, spec, cfg, row_index)
    row_results = run_batched_ga(rows, cfg, row_cache=row_cache)
    return assemble_model_result(layers, spec, row_index, seen, row_results,
                                 dedup)


def search_campaign(requests: Sequence[Tuple[Sequence[Layer], FlexSpec]],
                    cfg: Optional[GAConfig] = None,
                    dedup: bool = True,
                    row_cache=None) -> List[ModelResult]:
    """Campaign MSE: many whole-model searches — arbitrary (layers, spec)
    pairs sharing an HWConfig — as ONE engine row set.

    This is the batch shape of the paper's Sec 7 replay (one frozen design's
    variants swept across every future DNN): the engine packs all
    (model, spec, unique-layer) rows into full ``ROW_BUCKET`` chunks instead
    of padding each model/spec call separately, and with ``cfg.pipeline``
    each chunk's host draw prep overlaps the previous chunk's device
    compute.  Per-request results are bit-identical to per-request
    ``search_model`` calls: rows keep the same per-layer dedup and
    seed convention (``cfg.seed + 1000 * first_occurrence_index``), and rows
    are independent, so packing them differently changes nothing — which is
    also why a device pool (``cfg.devices`` / ``REPRO_DEVICES``) can spread
    the chunks without changing any result.  An empty campaign returns
    ``[]`` (it used to trip the engine's row assert).  ``row_cache`` (a
    ``ResultCache``) makes repeat rows — within this campaign or from any
    earlier cached call — skip their engine dispatch, results unchanged;
    it is how the DSE service shares rows across client requests."""
    cfg = cfg or GAConfig()
    requests = [(list(layers), spec) for layers, spec in requests]
    all_rows: List[EngineRow] = []
    meta: List[Tuple[List[int], Dict[tuple, int]]] = []
    for layers, spec in requests:
        row_index, seen = plan_model_rows(layers, dedup)
        meta.append((row_index, seen))
        all_rows.extend(request_rows(layers, spec, cfg, row_index))
    row_results = run_batched_ga(all_rows, cfg, row_cache=row_cache)
    out: List[ModelResult] = []
    pos = 0
    for (layers, spec), (row_index, seen) in zip(requests, meta):
        chunk = row_results[pos:pos + len(row_index)]
        pos += len(row_index)
        out.append(assemble_model_result(layers, spec, row_index, seen,
                                         chunk, dedup))
    return out


def _inert_mapping_rows(shape: Tuple[int, ...], native_bits: int = 8
                        ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                   np.ndarray, np.ndarray]:
    """Feasible placeholder mapping arrays for padded rows/models with any
    leading ``shape``: unit tiles, identity order, the (K, C) pair, a 1x1
    array, the native operand width.  One definition so every padded
    dispatch shares the same inert convention."""
    tiles = np.ones(shape + (NUM_DIMS,), np.int32)
    orders = np.tile(np.arange(NUM_DIMS, dtype=np.int32), shape + (1,))
    pairs = np.tile(np.asarray([0, 1], np.int32), shape + (1,))
    shapes = np.ones(shape + (2,), np.int32)
    reprs = np.full(shape, native_bits, np.int32)
    return tiles, orders, pairs, shapes, reprs


def _unpack(values: np.ndarray, packed: Sequence[int]) -> np.ndarray:
    """Undo a packing: ``values[k]`` belongs to row ``packed[k]``."""
    out = np.empty_like(values)
    out[np.asarray(packed, np.int64)] = values
    return out


def evaluate_fixed_genome_many(
        requests: Sequence[Tuple[Sequence[Layer], FlexSpec, np.ndarray]]
        ) -> List[ModelResult]:
    """Replay fixed mapping configs on many models in one chunked pass.

    Each request is ``(layers, spec, genome)``; all specs must share an
    HWConfig.  The (model, layer) rows of every request are flattened into
    one row list and evaluated through ``evaluate_rows`` in ``ROW_BUCKET``
    chunks, so the whole fig13 frozen-design replay — every future model —
    reuses one compiled program and a handful of dispatches.  With a device
    pool (``REPRO_DEVICES``) chunk *i* is committed to pool device ``i % D``
    and up to one chunk per device stays in flight (bounded backpressure —
    device memory never grows with the replay size), so the replay spreads
    over the pool.  Rows are independent, so per-request results are
    bit-identical to per-model :func:`evaluate_fixed_genome` calls —
    sharded or not."""
    reqs = [(list(layers), spec, np.asarray(genome))
            for layers, spec, genome in requests]
    if not reqs:
        return []
    hw = reqs[0][1].hw
    assert all(spec.hw == hw for _, spec, _ in reqs), \
        "replay requests must share an HWConfig"

    row_data = []          # per-row decoded arrays
    row_layers: List[Layer] = []
    mappings = []
    bounds: List[Tuple[int, int]] = []
    for layers, spec, genome in reqs:
        start = len(row_data)
        for layer in layers:
            space = mapspace_for(layer, spec)
            g = space.clip(genome[None, :])
            t, o, p, s, r = space.decode_batch(g)
            row_data.append((space.dims, layer.stride, layer.depthwise,
                             t[0], o[0], p[0], s[0], space.hard_partition,
                             r[0]))
            row_layers.append(layer)
            mappings.append(space.decode(g[0]))
        bounds.append((start, len(row_data)))

    pool = device_pool.default_pool()
    pieces: List[CostResult] = []

    def _materialize(n, res):
        pieces.append(CostResult(*(np.asarray(f)[:n] for f in res)))
        return ()

    # one in-flight chunk per pool device (1 without a pool) — async
    # round-robin dispatch with bounded backpressure, so device memory
    # stays at ~pool-depth chunks however large the replay is
    queue = InFlightQueue(depth=len(pool) if pool else 1,
                          collect=_materialize)
    # ragged rows replay in chunks of their own, through the ragged variant
    chunk_pos = pack_chunks(row_layers)
    for ci, pos in enumerate(chunk_pos):
        chunk = [row_data[i] for i in pos]
        n_pad = ROW_BUCKET
        dims = np.ones((n_pad, 6), np.int32)
        stride = np.ones(n_pad, np.int32)
        dw = np.zeros(n_pad, np.bool_)
        tiles, orders, pairs, shapes, reprs = _inert_mapping_rows(
            (n_pad,), 8 * hw.bytes_per_elem)
        hp = np.zeros(n_pad, np.bool_)
        for i, (d_, s_, w_, t, o, p, sh, h, r) in enumerate(chunk):
            dims[i], stride[i], dw[i] = d_, s_, w_
            tiles[i], orders[i], pairs[i], shapes[i], hp[i] = t, o, p, sh, h
            reprs[i] = r
        # all-native chunks replay through the pre-R program (v4 bit parity)
        r_live = bool((reprs != 8 * hw.bytes_per_elem).any())
        grouped, groups = _kind_args([row_layers[i] for i in pos], n_pad)
        args = (dims, stride, dw, tiles, orders, pairs, shapes, hp, reprs,
                grouped, groups)
        if pool is not None:
            args = pool.place(args, ci)
        queue.push(len(chunk),
                   evaluate_rows(*args[:8], hw,
                                 args[8] if r_live else None, *args[9:]))
    queue.drain()

    out: List[ModelResult] = []
    if pieces:
        packed = [i for pos in chunk_pos for i in pos]
        res = CostResult(*(_unpack(np.concatenate([p[f] for p in pieces]),
                                   packed)
                           for f in range(len(CostResult._fields))))
    for (start, end), _req in zip(bounds, reqs):
        per_layer = [MapperResult(
            mapping=mappings[j],
            runtime=float(res.runtime[j]), energy=float(res.energy[j]),
            edp=float(res.edp[j]), util=float(res.util[j]),
            dram_elems=float(res.dram_elems[j]),
            feasible=bool(res.feasible[j]), history=[])
            for j in range(start, end)]
        out.append(_model_result(per_layer))
    return out


def evaluate_fixed_genome(layers: Sequence[Layer], spec: FlexSpec,
                          genome: np.ndarray) -> ModelResult:
    """Run ONE mapping config on every layer (what an InFlex accel does).
    Layers evaluate in batched ``ROW_BUCKET``-padded dispatches so every
    model shares one compiled program; single-request case of
    :func:`evaluate_fixed_genome_many`."""
    return evaluate_fixed_genome_many([(layers, spec, genome)])[0]


def raw_tile_feasibility(tiles: jnp.ndarray,
                         buffer_elems: float) -> jnp.ndarray:
    """Hard-coded loop bounds must fit the buffer for ANY workload (tiles
    only ever clip DOWN on a layer): otherwise the hardened design would be
    unbuildable/unrunnable on future models.  tiles: (P, 6) raw genome tile
    genes; returns a (P,) bool mask."""
    t = tiles.astype(jnp.float32)
    in_vol = t[:, 1] * (t[:, 2] - 1 + t[:, 4]) * (t[:, 3] - 1 + t[:, 5])
    w_vol = t[:, 0] * t[:, 1] * t[:, 4] * t[:, 5]
    o_vol = t[:, 0] * t[:, 2] * t[:, 3]
    return (in_vol + w_vol + o_vol) <= buffer_elems


def _fixed_config_objective_impl(dims, strides, dws, mask, tiles, orders,
                                 pairs, shapes, reprs, hw,
                                 hard_partition: bool, objective: str,
                                 grouped=None, groups=None):
    """Whole-model objective of one shared mapping population — layer sweep,
    buffer-feasibility penalty and reduction all inside one jit (the serial
    version round-tripped raw tiles through host numpy every generation).
    ``grouped`` / ``groups``: the layers' kinds, as ``_kind_args`` gives
    them."""

    def per_layer(d, s, w, g, gr):
        # reprs None (native-pinned R) traces the pre-R program (v4 parity)
        def per_mapping(t1, o1, p1, s1, r1):
            return evaluate_kinds_impl(d, s, w, t1, o1, p1, s1, hw,
                                       hard_partition, r1, g, gr)
        return jax.vmap(per_mapping)(tiles, orders, pairs, shapes, reprs)

    res = jax.vmap(per_layer)(dims, strides, dws, grouped,
                              groups)                    # (L, P) fields
    m = mask[:, None].astype(jnp.float32)
    runtime = jnp.sum(res.runtime * m, axis=0)
    energy = jnp.sum(res.energy * m, axis=0)
    penalty = jnp.where(
        raw_tile_feasibility(tiles, jnp.float32(hw.buffer_elems)), 0.0, 1e30)
    runtime = runtime + penalty
    energy = energy + penalty
    return {"runtime": runtime, "energy": energy,
            "edp": runtime * energy}[objective]


@partial(jax.jit, static_argnames=("hw", "hard_partition", "objective"))
def _fixed_configs_objective(dims, strides, dws, mask, tiles, orders, pairs,
                             shapes, reprs, hw, hard_partition: bool,
                             objective: str, grouped=None, groups=None):
    """Model-stacked fixed-config objective: every array gains a leading
    model axis (one genome tensor per shape bucket), so a whole campaign of
    InFlex-0000-X-Opt designs evaluates in ONE dispatch per generation.
    vmap preserves the per-model arithmetic of
    ``_fixed_config_objective_impl``, so each model's (P,) objective is
    bit-identical to a per-model dispatch of that body (and results are
    independent of how many models share the stack)."""

    def one(d, s, w, m, t, o, p, sh, r, g, gr):
        return _fixed_config_objective_impl(d, s, w, m, t, o, p, sh, r, hw,
                                            hard_partition, objective, g, gr)

    with jax.named_scope("fixed_configs_objective"):
        return jax.vmap(one)(dims, strides, dws, mask, tiles, orders, pairs,
                             shapes, reprs, grouped, groups)


@dataclasses.dataclass
class _FixedConfigState:
    """Per-model host state of one fixed-config GA (campaign batching)."""

    layers: List[Layer]
    spec: FlexSpec
    space: MapSpace
    ops: _Operators
    rng: np.random.Generator
    dims: np.ndarray
    strides: np.ndarray
    dws: np.ndarray
    mask: np.ndarray
    pop: np.ndarray
    grouped: Optional[np.ndarray]
    groups: Optional[Tuple[np.ndarray, np.ndarray]]
    best_obj: float = np.inf
    best_g: Optional[np.ndarray] = None


def _fixed_config_state(layers: Sequence[Layer], spec: FlexSpec,
                        cfg: GAConfig) -> _FixedConfigState:
    """Build one model's GA state exactly as the single-model search did:
    same rng seeding order (state construction, then the population sample),
    so the campaign path consumes identical random streams."""
    rng = np.random.default_rng(cfg.seed)
    # use the largest layer's space for sampling bounds
    dims_mat = layers_as_array(layers)
    probe = Layer("probe", tuple(int(v) for v in dims_mat.max(axis=0)))
    space = MapSpace(probe, spec)
    ops = _Operators(space, cfg, rng)

    n = len(layers)
    n_pad = _bucket(max(n, 1), ROW_BUCKET)
    dims = np.ones((n_pad, 6), np.int32)
    dims[:n] = dims_mat
    strides = np.ones(n_pad, np.int32)
    strides[:n] = [l.stride for l in layers]
    dws = np.zeros(n_pad, np.bool_)
    dws[:n] = [l.depthwise for l in layers]
    mask = np.zeros(n_pad, np.bool_)
    mask[:n] = True
    pop = space.sample(rng, cfg.population)
    grouped, groups = _kind_args(layers, n_pad)
    return _FixedConfigState(layers=list(layers), spec=spec, space=space,
                             ops=ops, rng=rng, dims=dims, strides=strides,
                             dws=dws, mask=mask, pop=pop, grouped=grouped,
                             groups=groups)


def search_fixed_configs(
        requests: Sequence[Tuple[Sequence[Layer], FlexSpec]],
        cfg: Optional[GAConfig] = None
        ) -> List[Tuple[np.ndarray, ModelResult]]:
    """Fixed-config DSE for many models at once (fig13's InFlex-0000-X-Opt
    row as one campaign).

    Models are grouped into shape buckets — same padded layer count, same
    hard-partition flag, same program variant (plain, grouped, or ragged
    with the same group axis) — and each bucket's populations are stacked
    into one (M, P, 10) genome tensor: each generation is ONE
    ``_fixed_configs_objective`` dispatch for the whole bucket instead of
    one per model.  Selection,
    crossover and mutation stay host-side per model with each model's own
    Generator (seeded ``cfg.seed``, the single-model convention), so every
    model's genome trajectory — and therefore the returned design — is
    bit-identical to its own :func:`search_fixed_config` call."""
    cfg = cfg or GAConfig()
    requests = [(list(layers), spec) for layers, spec in requests]
    assert requests, "need at least one request"
    hw = requests[0][1].hw
    assert all(spec.hw == hw for _, spec in requests), \
        "fixed-config campaign requests must share an HWConfig"
    states = [_fixed_config_state(layers, spec, cfg)
              for layers, spec in requests]

    n_elite = ga_ops.n_elite(cfg)
    n_children = cfg.population - n_elite
    groups: Dict[tuple, List[_FixedConfigState]] = {}
    for st in states:
        key = (st.dims.shape[0], st.space.hard_partition,
               st.grouped is not None,
               None if st.groups is None else st.groups[0].shape)
        groups.setdefault(key, []).append(st)

    for (n_pad, hard, with_grouped, group_shape), group in groups.items():
        # the model axis is padded to a power of two so any campaign size
        # (1 model .. the full fig13 sweep) reuses a few compiled shapes;
        # pad slots hold inert feasible rows with an all-zero layer mask
        m = len(group)
        m_pad = _bucket(m, 1)
        dims_b = np.ones((m_pad, n_pad, 6), np.int32)
        strides_b = np.ones((m_pad, n_pad), np.int32)
        dws_b = np.zeros((m_pad, n_pad), np.bool_)
        mask_b = np.zeros((m_pad, n_pad), np.bool_)
        dims_b[:m] = [s.dims for s in group]
        strides_b[:m] = [s.strides for s in group]
        dws_b[:m] = [s.dws for s in group]
        mask_b[:m] = [s.mask for s in group]
        grouped_b = groups_b = None
        if with_grouped:
            grouped_b = np.zeros((m_pad, n_pad), np.bool_)
            grouped_b[:m] = [s.grouped for s in group]
        if group_shape is not None:
            gd_b = np.ones((m_pad,) + group_shape, np.int32)
            gl_b = np.zeros((m_pad,) + group_shape[:2], np.bool_)
            gl_b[:, :, 0] = True
            gd_b[:m] = [s.groups[0] for s in group]
            gl_b[:m] = [s.groups[1] for s in group]
            groups_b = (gd_b, gl_b)
        tiles_b, orders_b, pairs_b, shapes_b, reprs_b = _inert_mapping_rows(
            (m_pad, cfg.population), 8 * hw.bytes_per_elem)
        for _ in range(cfg.generations):
            with tracing.span("design.decode"):
                for mi, s in enumerate(group):
                    (tiles_b[mi], orders_b[mi], pairs_b[mi],
                     shapes_b[mi], reprs_b[mi]) = s.space.decode_batch(s.pop)
                r_live = bool((reprs_b != 8 * hw.bytes_per_elem).any())
            with tracing.span("design.objective", dispatches=1):
                obj_b = np.asarray(_fixed_configs_objective(
                    dims_b, strides_b, dws_b, mask_b,
                    jnp.asarray(tiles_b), jnp.asarray(orders_b),
                    jnp.asarray(pairs_b), jnp.asarray(shapes_b),
                    jnp.asarray(reprs_b) if r_live else None,
                    hw=hw, hard_partition=hard, objective=cfg.objective,
                    grouped=grouped_b, groups=groups_b))
            with tracing.span("design.breed"):
                for s, obj in zip(group, obj_b):
                    order_idx = np.argsort(obj, kind="stable")
                    if obj[order_idx[0]] < s.best_obj:
                        s.best_obj = float(obj[order_idx[0]])
                        s.best_g = s.pop[order_idx[0]].copy()
                    elites = s.pop[order_idx[:n_elite]]
                    ranks = s.rng.choice(cfg.population, n_children,
                                         p=ga_ops.rank_probs(cfg.population))
                    children = s.ops.mutate(s.ops.crossover(
                        s.pop[order_idx[ranks]]))
                    s.pop = np.concatenate([elites, children], axis=0)

    assert all(s.best_g is not None for s in states)
    replays = evaluate_fixed_genome_many(
        [(s.layers, s.spec, s.best_g) for s in states])
    return [(s.best_g, r) for s, r in zip(states, replays)]


def search_fixed_config(layers: Sequence[Layer], spec: FlexSpec,
                        cfg: Optional[GAConfig] = None
                        ) -> Tuple[np.ndarray, ModelResult]:
    """DSE for an *inflexible* accelerator: find the single TOPS config that
    minimizes whole-model runtime (paper Sec 7, InFlex-0000-X-Opt).

    The genome is shared across layers; per-layer tile clipping applies.
    Layers are padded to the engine row bucket so every model reuses one
    compiled objective.  Single-model case of :func:`search_fixed_configs`."""
    return search_fixed_configs([(layers, spec)], cfg)[0]
