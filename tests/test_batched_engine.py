"""Golden-parity and behaviour tests for the batched MSE engine.

The contract: with a fixed seed and identical GAConfig, the engine and the
per-layer reference GA (tests/_reference_ga.py) return *identical* results
per layer — any silent cost-model or operator drift in the engine trips
these tests.
"""
import re

import numpy as np
import pytest

from repro.core import (FULLFLEX, GAConfig, PARTFLEX, inflex_baseline,
                        make_variant, run_dse, search, search_campaign,
                        search_model)
from repro.core import engine, ga_ops, tracing
from repro.core import mapper as mapper_mod
from repro.core.mapper import plan_model_rows, request_rows
from repro.core.mapspace import mapspace_for
from repro.core.engine import ROW_BUCKET, EngineRow, run_batched_ga
from repro.core.workloads import Layer, get_model

import _reference_ga
from _reference_ga import run_rows, search_layer

# the paper's quoted MnasNet layers 1 and 29
LAYER1 = Layer("mnas.layer1", (32, 3, 224, 224, 3, 3))
LAYER29 = Layer("mnas.layer29", (1, 480, 14, 14, 5, 5), depthwise=True)
LAYERS = [LAYER1, LAYER29]

CFG = GAConfig(population=16, generations=6, seed=7)

SPECS = {
    "InFlex": inflex_baseline(),
    "PartFlex": make_variant("1111", PARTFLEX),
    "FullFlex": make_variant("1111", FULLFLEX),
}


def _assert_identical(a, b):
    """Exact (bitwise) agreement of two MapperResults."""
    assert a.runtime == b.runtime
    assert a.energy == b.energy
    assert a.edp == b.edp
    assert a.util == b.util
    assert a.dram_elems == b.dram_elems
    assert a.feasible == b.feasible
    assert a.history == b.history
    assert a.mapping == b.mapping


def _on_reference(monkeypatch, fn, *args, **kwargs):
    """``fn(*args, **kwargs)`` with the engine replaced by the reference GA."""
    with monkeypatch.context() as m:
        m.setattr(mapper_mod, "run_batched_ga", run_rows)
        return fn(*args, **kwargs)


@pytest.mark.parametrize("flex", sorted(SPECS))
def test_golden_parity_search_model(flex, monkeypatch):
    spec = SPECS[flex]
    ref = _on_reference(monkeypatch, search_model, LAYERS, spec, CFG)
    batched = search_model(LAYERS, spec, CFG)
    assert ref.runtime == batched.runtime
    assert ref.energy == batched.energy
    for rs, rb in zip(ref.per_layer, batched.per_layer):
        _assert_identical(rs, rb)


def test_golden_parity_single_layer_search():
    for spec in SPECS.values():
        _assert_identical(search_layer(LAYER29, spec, CFG),
                          search(LAYER29, spec, CFG))


# one layer of each kind the engine's program variants trace differently
KIND_CASES = {
    "depthwise": (LAYER29, ("1111", "0101")),
    "grouped": (next(l for l in get_model("kimi-k2-decode32k")
                     if l.name == "L4.scores"), ("1111", "0101")),
    "ragged": (next(l for l in get_model("kimi-k2-decode32k")
                    if l.name == "L3.experts.gate_up"), ("1111", "0101")),
    "r-open": (LAYER1, ("11111",)),
}


@pytest.mark.parametrize("kind", list(KIND_CASES))
def test_engine_matches_reference_ga(kind):
    """``search`` on the engine equals the reference GA field by field for
    every layer kind (the grouped and ragged variants included)."""
    layer, classes = KIND_CASES[kind]
    assert layer.grouped == (kind in ("grouped", "ragged"))
    assert layer.ragged == (kind == "ragged")
    cfg = GAConfig(population=12, generations=4, seed=7)
    for cs in classes:
        spec = make_variant(cs, FULLFLEX)
        _assert_identical(search_layer(layer, spec, cfg),
                          search(layer, spec, cfg))


def test_search_campaign_matches_per_spec():
    specs = [SPECS["InFlex"], SPECS["FullFlex"]]
    combined = search_campaign([(LAYERS, spec) for spec in specs], CFG)
    for spec, mres in zip(specs, combined):
        solo = search_model(LAYERS, spec, CFG)
        assert mres.runtime == solo.runtime
        for ra, rb in zip(mres.per_layer, solo.per_layer):
            _assert_identical(ra, rb)


def test_run_dse_batches_shared_hw_candidates():
    specs = [SPECS["InFlex"], SPECS["PartFlex"]]
    rows = run_dse(LAYERS, specs, CFG)
    for spec, r in zip(specs, rows):
        solo = search_model(LAYERS, spec, CFG)
        assert r.runtime == solo.runtime


def test_dedup_shares_search_across_equal_shapes(monkeypatch):
    """Two layers with equal (dims, stride, depthwise) but different names
    must share ONE search (regression for the dedup cache key)."""
    twins = [Layer("conv_a", (64, 32, 28, 28, 3, 3)),
             Layer("conv_b_other_name", (64, 32, 28, 28, 3, 3))]
    spec = SPECS["FullFlex"]

    calls = []
    real = mapper_mod.run_batched_ga

    def counting(rows, cfg, row_cache=None):
        calls.append(len(rows))
        return real(rows, cfg, row_cache=row_cache)

    monkeypatch.setattr(mapper_mod, "run_batched_ga", counting)
    res = search_model(twins, spec, CFG)
    assert calls == [1]                       # one engine row for both
    assert res.per_layer[0] is res.per_layer[1]

    # reference GA: one per-layer search for the pair
    ref_calls = []
    real_search = _reference_ga._search

    def counting_search(layer, sp, cfg):
        ref_calls.append(layer.name)
        return real_search(layer, sp, cfg)

    monkeypatch.setattr(_reference_ga, "_search", counting_search)
    monkeypatch.setattr(mapper_mod, "run_batched_ga", run_rows)
    res_s = search_model(twins, spec, CFG)
    assert ref_calls == ["conv_a"]
    assert res_s.per_layer[0] is res_s.per_layer[1]


def test_dedup_off_matches_dedup_on_for_unique_layers():
    layers = get_model("ncf")  # all-unique GEMM tower
    spec = SPECS["FullFlex"]
    a = search_model(layers, spec, CFG, dedup=True)
    b = search_model(layers, spec, CFG, dedup=False)
    assert a.runtime == b.runtime


# -- the chunk's row draws on the draw pool ---------------------------------

# R-pinned and R-open, InFlex, PartFlex and FullFlex specs in one chunk
CHUNK_SPECS = [inflex_baseline(), make_variant("1111", FULLFLEX),
               make_variant("11111", FULLFLEX),
               make_variant("11111", PARTFLEX)]


def _chunk_rows(n):
    layers = get_model("mnasnet")
    return [EngineRow(layers[i % len(layers)],
                      CHUNK_SPECS[i % len(CHUNK_SPECS)], seed=7 + 1000 * i)
            for i in range(n)]


def _assert_chunks_equal(a, b):
    assert a.gens == b.gens
    for name in engine.ChunkInputs._fields:
        x, y = getattr(a, name), getattr(b, name)
        if name == "draws":
            for field in x._fields:
                assert np.array_equal(getattr(x, field),
                                      getattr(y, field)), field
        elif name != "gens":
            assert (x is None) == (y is None), name
            assert x is None or np.array_equal(x, y), name


@pytest.mark.parametrize("n_rows", [1, 7, ROW_BUCKET])
def test_pooled_draws_match_inline_draws(monkeypatch, n_rows):
    """Every array of a chunk drawn on several threads equals the one a
    single thread draws: each row keeps its own Generator stream."""
    rows = _chunk_rows(n_rows)
    hw = rows[0].spec.hw
    monkeypatch.setattr(engine, "_draw_workers",
                        lambda n: min(n, engine.DRAW_WORKERS))
    pooled = engine._prepare_chunk(rows, CFG, hw)
    monkeypatch.setattr(engine, "_draw_workers", lambda n: 1)
    inline = engine._prepare_chunk(rows, CFG, hw)
    _assert_chunks_equal(pooled, inline)


@pytest.mark.parametrize("bad_row", [5, ROW_BUCKET + 5])
def test_draw_error_reaches_the_chunk_handler(monkeypatch, bad_row):
    """A row whose map space raises on a draw thread fails its chunk with
    the chunk's context, after every dispatched chunk was collected."""
    rows = [EngineRow(Layer(f"l{i}", (8, 4, 6, 6, 3, 3)),
                      make_variant("1111"), seed=i)
            for i in range(ROW_BUCKET + 6)]
    real_mapspace = engine.mapspace_for

    def failing_mapspace(layer, spec):
        if layer.name == f"l{bad_row}":
            raise ValueError("bad map space")
        return real_mapspace(layer, spec)

    queues, collected = [], []
    real_queue, real_collect = engine.InFlightQueue, engine._collect_chunk

    def spy_queue(*args, **kwargs):
        queues.append(real_queue(*args, **kwargs))
        return queues[-1]

    def spy_collect(n_rows, gens, outputs):
        collected.append(n_rows)
        return real_collect(n_rows, gens, outputs)

    monkeypatch.setattr(engine, "mapspace_for", failing_mapspace)
    monkeypatch.setattr(engine, "InFlightQueue", spy_queue)
    monkeypatch.setattr(engine, "_collect_chunk", spy_collect)
    cfg = GAConfig(population=8, generations=4, seed=3, pipeline=True)
    idx = bad_row // ROW_BUCKET
    with pytest.raises(RuntimeError,
                       match=f"engine chunk {idx}/2 .* failed during "
                             f"prepare/dispatch") as err:
        run_batched_ga(rows, cfg)
    assert isinstance(err.value.__cause__, ValueError)
    assert len(queues) == 1 and len(queues[0]) == 0
    assert collected == [ROW_BUCKET] * idx


# -- one draw stream per (seed, R test) within a call ------------------------

def _per_row_draws(rows, cfg):
    """A chunk's ``pop0`` and draw stack drawn the per-row way: each row's
    own Generator, ``initial_population`` then ``draw_run``."""
    n_children = cfg.population - ga_ops.n_elite(cfg)
    gens = cfg.generations
    gens_pad = engine._bucket(max(gens, 1), engine.GEN_BUCKET)
    pop0 = np.ones((ROW_BUCKET, cfg.population, ga_ops.GENOME_LEN),
                   np.int32)
    stack = ga_ops.empty_draw_stack(gens_pad, ROW_BUCKET, n_children)
    for i, row in enumerate(rows):
        space = mapspace_for(row.layer, row.spec)
        rng = np.random.default_rng(row.seed)
        pop0[i] = ga_ops.initial_population(rng, space, cfg)
        for field, stacked in zip(
                ga_ops.draw_run(rng, space, cfg, gens, n_children), stack):
            stacked[:gens, i] = field
    return pop0, stack


def _assert_per_row_draws(chunk, rows, cfg):
    pop0, stack = _per_row_draws(rows, cfg)
    assert np.array_equal(chunk.pop0, pop0)
    for field in stack._fields:
        assert np.array_equal(getattr(chunk.draws, field),
                              getattr(stack, field)), field


def _campaign_rows(models, classes, cfg, n_layers=6):
    """A campaign's rows: the seed convention repeats each seed across the
    requests' specs and models."""
    rows = []
    for model in models:
        layers = get_model(model)[:n_layers]
        row_index, _ = plan_model_rows(layers)
        for cs, flex in classes:
            rows += request_rows(layers, make_variant(cs, flex), cfg,
                                 row_index)
    return rows


def _kimi_rows(ragged, cfg):
    layers = [l for l in get_model("kimi-k2-decode32k")
              if l.grouped and l.ragged == ragged][:4]
    return [r for spec in (make_variant("1111"), make_variant("0101"))
            for r in request_rows(layers, spec, cfg, range(len(layers)))]


MEMO_CASES = {
    # one seed per layer position, read by 3 specs of 2 models
    "shared-seeds": lambda cfg: _campaign_rows(
        ("mnasnet", "resnet50"),
        (("1111", FULLFLEX), ("1111", PARTFLEX), ("0101", FULLFLEX)), cfg),
    # the same seeds R-pinned and R-open: two streams each
    "r-open-and-pinned": lambda cfg: _campaign_rows(
        ("mnasnet",), (("1111", FULLFLEX), ("11111", FULLFLEX)), cfg),
    "grouped": lambda cfg: _kimi_rows(False, cfg),
    "ragged": lambda cfg: _kimi_rows(True, cfg),
    "one-row": lambda cfg: _campaign_rows(
        ("mnasnet",), (("11111", FULLFLEX),), cfg, n_layers=1),
}


@pytest.mark.parametrize("case", list(MEMO_CASES))
def test_shared_streams_equal_per_row_draws(case):
    """A chunk drawn from shared streams equals the per-row draws, and
    draws each distinct (seed, R test) stream once."""
    rows = MEMO_CASES[case](CFG)
    assert 1 <= len(rows) <= ROW_BUCKET
    hw = rows[0].spec.hw
    sink = {}
    with tracing.recording(sink):
        chunk = engine._prepare_chunk(rows, CFG, hw)
    _assert_per_row_draws(chunk, rows, CFG)
    keys = {engine._stream_key(r) for r in rows}
    assert sink["engine.prepare.draws:streams"] == len(keys)
    if case == "r-open-and-pinned":
        seeds = {r.seed for r in rows}
        assert len(keys) == 2 * len(seeds)
    elif case != "one-row":
        assert len(keys) < len(rows)
    if case in ("grouped", "ragged"):
        assert chunk.grouped is not None
        assert (chunk.group_dims is not None) == (case == "ragged")


@pytest.mark.parametrize("pipeline", [False, True])
def test_stream_memo_carries_across_the_chunks_of_a_call(monkeypatch,
                                                         pipeline):
    """One call's chunks share its memo: each stream is drawn once for the
    call, and every chunk equals the per-row draws and the same chunk
    prepared alone."""
    rows = _campaign_rows(("mnasnet", "mobilenetv2"),
                          (("1111", FULLFLEX), ("11111", FULLFLEX),
                           ("0101", PARTFLEX)), CFG, n_layers=12)
    assert len(rows) > ROW_BUCKET
    prepared = []
    real = engine._prepare_chunk

    def spy(chunk_rows, cfg, hw, memo=None):
        assert memo is not None
        prepared.append((list(chunk_rows), real(chunk_rows, cfg, hw, memo)))
        return prepared[-1][1]

    monkeypatch.setattr(engine, "_prepare_chunk", spy)
    sink = {}
    with tracing.recording(sink):
        run_batched_ga(rows, GAConfig(population=CFG.population,
                                      generations=CFG.generations,
                                      seed=CFG.seed, pipeline=pipeline))
    assert len(prepared) == 2
    assert sink["engine.prepare.draws:streams"] == len(
        {engine._stream_key(r) for r in rows})
    monkeypatch.setattr(engine, "_prepare_chunk", real)
    for chunk_rows, chunk in prepared:
        _assert_per_row_draws(chunk, chunk_rows, CFG)
        _assert_chunks_equal(chunk, real(chunk_rows, CFG, chunk_rows[0]
                                         .spec.hw))


@pytest.mark.parametrize("stage", ["stream", "row"])
def test_stream_error_reaches_the_chunk_handler(monkeypatch, stage):
    """An error in a stream draw (on a draw thread) or in a row's
    derivation fails its chunk with the chunk's context."""
    rows = [EngineRow(Layer(f"l{i}", (8, 4, 6, 6, 3, 3)),
                      make_variant("1111"), seed=i)
            for i in range(ROW_BUCKET + 6)]
    bad = ROW_BUCKET + 2
    name = "draw_stream" if stage == "stream" else "row_draws"
    real = getattr(ga_ops, name)

    def failing(first, *args):
        if (first == bad if stage == "stream"
                else args[0].layer.name == f"l{bad}"):
            raise ValueError(f"bad {stage}")
        return real(first, *args)

    monkeypatch.setattr(ga_ops, name, failing)
    cfg = GAConfig(population=8, generations=4, seed=3, pipeline=True)
    with pytest.raises(RuntimeError,
                       match="engine chunk 1/2 .* failed during "
                             "prepare/dispatch") as err:
        run_batched_ga(rows, cfg)
    assert isinstance(err.value.__cause__, ValueError)


def _evaluate_gathers(hlo: str):
    """``(operand shape, indexed axes)`` of every gather in the ``evaluate``
    (and ``evaluate_ragged``) scope of an HLO module's text."""
    shapes = {name: tuple(int(d) for d in dims.split(",") if d)
              for name, dims in re.findall(
                  r"^\s*(?:ROOT\s+)?([\w.\-]+) = \w+\[([\d,]*)\]", hlo,
                  re.M)}
    out = []
    for line in hlo.splitlines():
        if " gather(" in line and re.search(
                r'op_name="[^"]*/evaluate(_ragged)?/', line):
            operand = re.search(r" gather\(([^,\s]+)", line).group(1)
            axes = re.search(r"start_index_map=\{([\d,]*)\}", line).group(1)
            out.append((shapes[operand],
                        tuple(int(a) for a in axes.split(","))))
    return out


@pytest.mark.parametrize("kind", ["plain", "repr", "grouped", "ragged"])
def test_evaluate_gathers_only_the_decode_tables(kind):
    """The cost model reads its per-dimension vectors at traced indices
    (``order``, ``par``) by compare and select: the lowered GA programs'
    ``evaluate`` scope gathers from the chunk's decode tables and from no
    length-6 per-dimension operand (docs/mapper.md "Batched engine
    dataflow")."""
    from repro.core import HWConfig
    from repro.core.mapper import plan_model_rows, request_rows
    cfg, hw = GAConfig(population=8, generations=2), HWConfig()
    if kind in ("plain", "repr"):
        layers = get_model("mnasnet")[:4]
        spec = make_variant("11111" if kind == "repr" else "1111", hw=hw)
    else:
        layers = [l for l in get_model("kimi-k2-decode32k")
                  if l.ragged == (kind == "ragged")]
        spec = make_variant("1111", hw=hw)
    row_index, _ = plan_model_rows(layers)
    c = engine._prepare_chunk(request_rows(layers, spec, cfg, row_index),
                              cfg, hw)
    assert (c.grouped is not None) == (kind in ("grouped", "ragged"))
    program = engine._ga_program_ragged if kind == "ragged" \
        else engine._ga_program
    tail = (c.group_dims, c.group_live) if kind == "ragged" else ()
    hlo = program.lower(
        c.dims, c.stride, c.depthwise, c.tile_lo, c.tile_hi,
        c.hard_partition, c.table_id, c.orders, c.pairs, c.shapes, c.reprs,
        c.lens, c.pop0, c.draws, np.int32(c.gens), c.grouped, *tail, hw=hw,
        n_elite=ga_ops.n_elite(cfg), objective=cfg.objective,
        with_repr=kind == "repr").as_text(dialect="hlo", debug_info=True)
    gathers = _evaluate_gathers(hlo)
    per_dim = [g for g in gathers if any(g[0][a] == 6 for a in g[1])]
    assert not per_dim, per_dim
    tables = [c.orders, c.pairs, c.shapes] + [c.reprs] * (kind == "repr")
    assert sorted(shape for shape, _ in gathers) == \
        sorted(t.shape for t in tables)
