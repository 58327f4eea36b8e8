"""The grouped and ragged layer kinds (docs/mapper.md "Layer kinds") and the
Kimi-K2 decode model built from them.

The program's cost of each kind — in the engine, the fixed-config objective
and the frozen replay — is held to the plain float64 reference kept with
the benchmark (``bench/reference/kinds.py``, loaded by path) on seeded
random mappings, within 1e-5 relative: float32 against float64, the limit
the benchmark's ``cost_gap`` holds.  The identities of the kinds are held
bit for bit or to float32 rounding, and the kind keeps a grouped layer and
a plain GEMM of the same dims apart wherever results are keyed.
"""
import dataclasses
import importlib.util
import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FlexSpec, GAConfig, HWConfig, ResultCache,
                        evaluate_fixed_genome, evaluate_mapping,
                        evaluate_rows, future_proofing_study, gemm,
                        get_model, grouped_gemm, make_variant, ragged_gemm,
                        row_cache_key, run_batched_ga, workload_for_layer)
from repro.core import engine, tracing
from repro.core.flexion import compute_flexion
from repro.core.mapper import (_dedup_key, _fixed_configs_objective,
                               _kind_args, plan_model_rows,
                               raw_tile_feasibility, request_rows)
from repro.core.mapspace import mapspace_for
from repro.core.workloads import (KIMI_K2_EXPERT_LOADS, Layer, expert_loads,
                                  group_table)
from repro.serve.dse_service import DSEService

ROOT = Path(__file__).resolve().parents[1]
HW = HWConfig()
HW_DICT = dataclasses.asdict(HW)
SPEC = make_variant("11111", hw=HW)     # every axis open, R included
CFG = GAConfig(population=16, generations=4)
RTOL = 1e-5

GROUPED = grouped_gemm("g", 6, 96, 20, 40)
RAGGED = ragged_gemm("r", 72, (5, 37, 0, 12, 9), 48)
PLAIN = gemm("p", 96, 20, 40)
DW = Layer("dw", (1, 24, 14, 14, 3, 3), stride=2, depthwise=True)


def _load(name):
    path = ROOT / "bench" / "reference" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"reference_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


KINDS = _load("kinds")


def _reference(layer, mappings, hard):
    """Float64 reference runtime and energy of ``layer`` under each
    ``Mapping``."""
    m = {"dims": np.asarray([layer.dims] * len(mappings)),
         "stride": np.full(len(mappings), layer.stride),
         "tiles": np.asarray([mp.tiles for mp in mappings]),
         "order": np.asarray([mp.order for mp in mappings]),
         "par": np.asarray([mp.parallel for mp in mappings]),
         "shape": np.asarray([mp.shape for mp in mappings]),
         "bits": np.asarray([mp.repr_bits for mp in mappings]),
         "hard": np.full(len(mappings), hard)}
    if layer.ragged:
        rt, en, ok = KINDS.ragged_costs(m, [layer.group_rows] * len(m["dims"]),
                                        HW_DICT)
    else:
        rt, en, ok = KINDS.grouped_costs(m, HW_DICT)
    return np.asarray(rt), np.asarray(en), np.asarray(ok)


def _mappings(layer, spec, n, seed):
    space = mapspace_for(layer, spec)
    genomes = space.clip(space.sample(np.random.default_rng(seed), n))
    return space, genomes, [space.decode(g) for g in genomes]


def _rows_costs(layers, spec, genomes_per_layer):
    """One ``evaluate_rows`` dispatch of one mapping per row."""
    tiles, orders, pairs, shapes, bits = [], [], [], [], []
    for layer, g in zip(layers, genomes_per_layer):
        t, o, p, s, r = mapspace_for(layer, spec).decode_batch(g[None])
        for acc, v in zip((tiles, orders, pairs, shapes, bits),
                          (t, o, p, s, r)):
            acc.append(v[0])
    grouped, groups = _kind_args(layers)
    return evaluate_rows(
        jnp.asarray([l.dims for l in layers]),
        jnp.asarray([l.stride for l in layers]),
        jnp.asarray([l.depthwise for l in layers]),
        jnp.asarray(tiles), jnp.asarray(orders), jnp.asarray(pairs),
        jnp.asarray(shapes),
        jnp.asarray([mapspace_for(l, spec).hard_partition for l in layers]),
        HW, jnp.asarray(bits), grouped, groups)


# -- the program against the reference -------------------------------------

@pytest.mark.parametrize("level", ["full", "part"])
@pytest.mark.parametrize("layer", [GROUPED, RAGGED], ids=["grouped",
                                                          "ragged"])
def test_engine_costs_match_reference(layer, level):
    """The engine's best mapping of a row, costed by the program, against
    the reference for that mapping."""
    spec = make_variant("11111", level, hw=HW)
    res = run_batched_ga([engine.EngineRow(layer, spec, 7)], CFG)[0]
    space = mapspace_for(layer, spec)
    rt, en, ok = _reference(layer, [space.decode(res.best_genome)],
                            space.hard_partition)
    assert res.feasible == bool(ok[0])
    np.testing.assert_allclose([res.runtime, res.energy], [rt[0], en[0]],
                               rtol=RTOL)


@pytest.mark.parametrize("level", ["full", "part"])
def test_replay_costs_match_reference(level):
    spec = make_variant("11111", level, hw=HW)
    layers = [GROUPED, RAGGED, PLAIN, GROUPED]
    for seed in range(3):
        genome = _mappings(GROUPED, spec, 1, seed)[1][0]
        res = evaluate_fixed_genome(layers, spec, genome)
        for layer, r in zip(layers, res.per_layer):
            if layer.kind == "plain":
                continue
            rt, en, ok = _reference(layer, [r.mapping],
                                    mapspace_for(layer, spec).hard_partition)
            assert r.feasible == bool(ok[0])
            np.testing.assert_allclose([r.runtime, r.energy], [rt[0], en[0]],
                                       rtol=RTOL)


@pytest.mark.parametrize("layer", [GROUPED, RAGGED], ids=["grouped",
                                                          "ragged"])
def test_rows_match_reference_on_random_mappings(layer):
    """Seeded random mappings of each kind, one ``evaluate_rows`` dispatch,
    against the reference: the feasible ones within RTOL, and the same
    feasibility for all."""
    space, genomes, mappings = _mappings(layer, SPEC, 256, 11)
    res = _rows_costs([layer] * len(genomes), SPEC, genomes)
    rt, en, ok = _reference(layer, mappings, False)
    np.testing.assert_array_equal(np.asarray(res.feasible), ok)
    assert ok.sum() > 10
    np.testing.assert_allclose(np.asarray(res.runtime)[ok], rt[ok],
                               rtol=RTOL)
    np.testing.assert_allclose(np.asarray(res.energy)[ok], en[ok], rtol=RTOL)


def test_fixed_config_objective_matches_reference():
    """The fixed-config design's whole-model objective (one shared mapping
    over every layer, kinds mixed) against the reference's sum."""
    layers = [GROUPED, RAGGED, GROUPED]
    probe = Layer("probe", tuple(int(v) for v in
                                 np.max([l.dims for l in layers], axis=0)))
    space, genomes, _ = _mappings(probe, SPEC, 64, 3)
    t, o, p, s, r = space.decode_batch(genomes)
    n_pad = 64
    dims = np.ones((1, n_pad, 6), np.int32)
    dims[0, :3] = [l.dims for l in layers]
    mask = np.zeros((1, n_pad), np.bool_)
    mask[0, :3] = True
    grouped, (gd, gl) = _kind_args(layers, n_pad)
    obj = np.asarray(_fixed_configs_objective(
        dims, np.ones((1, n_pad), np.int32), np.zeros((1, n_pad), np.bool_),
        mask, jnp.asarray(t[None]), jnp.asarray(o[None]),
        jnp.asarray(p[None]), jnp.asarray(s[None]), jnp.asarray(r[None]),
        hw=HW, hard_partition=False, objective="runtime",
        grouped=grouped[None], groups=(gd[None], gl[None])))[0]
    want = np.zeros(len(genomes))
    for layer in layers:
        lspace = mapspace_for(layer, SPEC)
        maps = [lspace.decode(g) for g in lspace.clip(genomes)]
        want += _reference(layer, maps, False)[0]
    raw_ok = np.asarray(raw_tile_feasibility(jnp.asarray(t),
                                             HW.buffer_elems))
    live = raw_ok & (want < 1e29)
    assert live.sum() > 5
    np.testing.assert_allclose(obj[live], want[live], rtol=RTOL)
    assert np.all(obj[~live] >= 1e29)


# -- identities of the kinds --------------------------------------------------

def test_grouped_with_one_group_is_the_plain_gemm_bit_for_bit():
    one = grouped_gemm("g1", 1, 96, 20, 40)
    assert one.dims == PLAIN.dims
    _, genomes, _ = _mappings(PLAIN, SPEC, 64, 5)
    res = _rows_costs([one] * 64 + [PLAIN] * 64, SPEC,
                      np.concatenate([genomes, genomes]))
    for f in res:
        np.testing.assert_array_equal(np.asarray(f)[:64],
                                      np.asarray(f)[64:])
    a, b = run_batched_ga([engine.EngineRow(one, SPEC, 3),
                           engine.EngineRow(PLAIN, SPEC, 3)], CFG)
    np.testing.assert_array_equal(a.best_genome, b.best_genome)
    assert a[1:] == b[1:]


def test_ragged_with_equal_loads_is_g_times_one_group():
    g, n = 6, 24
    ragged = ragged_gemm("eq", 72, [n] * g, 48)
    one = gemm("one", 72, n, 48)
    _, genomes, _ = _mappings(one, SPEC, 64, 9)
    res = _rows_costs([ragged] * 64 + [one] * 64, SPEC,
                      np.concatenate([genomes, genomes]))
    rt, en = np.asarray(res.runtime), np.asarray(res.energy)
    ok = np.asarray(res.feasible)
    np.testing.assert_array_equal(ok[:64], ok[64:])
    np.testing.assert_allclose(rt[:64][ok[:64]], g * rt[64:][ok[64:]],
                               rtol=1e-6)
    np.testing.assert_allclose(en[:64][ok[:64]], g * en[64:][ok[64:]],
                               rtol=1e-6)


def test_ragged_is_the_sum_of_its_experts_under_one_mapping():
    experts = [gemm(f"e{i}", 72, n, 48)
               for i, n in enumerate(RAGGED.group_rows) if n]
    _, genomes, mappings = _mappings(RAGGED, SPEC, 48, 13)
    res = _rows_costs([RAGGED] * len(genomes), SPEC, genomes)
    want_rt = np.zeros(len(genomes))
    want_en = np.zeros(len(genomes))
    want_ok = np.ones(len(genomes), bool)
    for e in experts:
        for j, mp in enumerate(mappings):
            r = evaluate_mapping(
                jnp.asarray(e.dims), jnp.asarray(1), jnp.asarray(False),
                jnp.asarray(mp.tiles), jnp.asarray(mp.order),
                jnp.asarray(mp.parallel), jnp.asarray(mp.shape), hw=HW,
                repr_bits=jnp.float32(mp.repr_bits))
            want_rt[j] += float(r.runtime)
            want_en[j] += float(r.energy)
            want_ok[j] &= bool(r.feasible)
    np.testing.assert_array_equal(np.asarray(res.feasible), want_ok)
    np.testing.assert_allclose(np.asarray(res.runtime)[want_ok],
                               want_rt[want_ok], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(res.energy)[want_ok],
                               want_en[want_ok], rtol=1e-6)


def test_ragged_map_space_runs_one_group_at_a_time():
    space = mapspace_for(RAGGED, SPEC)
    assert tuple(space.tile_hi) == (72, 48, 37, 1, 1, 1)
    assert RAGGED.macs == 72 * 48 * sum(RAGGED.group_rows)
    gd, gl = group_table([RAGGED, PLAIN], 4)
    assert gl.sum(axis=1).tolist() == [4, 1, 1, 1]
    assert [tuple(d) for d in gd[0][gl[0]]] == list(RAGGED.group_dims())


def test_layer_kind_validation():
    with pytest.raises(ValueError):
        Layer("bad", (4, 4, 4, 2, 1, 1), grouped=True, group_rows=(4, 3, 1))
    with pytest.raises(ValueError):
        Layer("bad", (4, 4, 5, 2, 1, 1), grouped=True, group_rows=(4, 3))
    with pytest.raises(ValueError):
        Layer("bad", (4, 4, 4, 2, 1, 1), depthwise=True, grouped=True)


# -- keys: a grouped layer is not a plain GEMM --------------------------------

TWIN = grouped_gemm("twin", 1, 96, 20, 40)


def test_kind_enters_dedup_and_row_keys():
    assert TWIN.dims == PLAIN.dims
    assert _dedup_key(TWIN) != _dedup_key(PLAIN)
    row_index, _ = plan_model_rows([PLAIN, TWIN])
    assert row_index == [0, 1]
    rows = request_rows([PLAIN, TWIN], SPEC, CFG, row_index)
    assert row_cache_key(rows[0], CFG) != row_cache_key(
        dataclasses.replace(rows[1], seed=rows[0].seed), CFG)
    rag = ragged_gemm("r2", 72, (5, 37, 12, 9, 0), 48)
    assert rag.dims == RAGGED.dims and _dedup_key(rag) != _dedup_key(RAGGED)


def test_result_cache_and_service_keep_kinds_apart():
    cache = ResultCache()
    rows = [engine.EngineRow(PLAIN, SPEC, 1), engine.EngineRow(TWIN, SPEC, 1)]
    run_batched_ga(rows, CFG, row_cache=cache)
    assert cache.stats()["size"] == 2
    with DSEService() as svc:
        a = svc.submit([PLAIN], SPEC, CFG)
        b = svc.submit([TWIN], SPEC, CFG)
        a.result(timeout=120), b.result(timeout=120)
        assert svc.stats()["rows_dispatched"] == 2


def test_engine_packs_ragged_rows_apart():
    rows = [engine.EngineRow(l, SPEC, 100 + i)
            for i, l in enumerate([RAGGED, PLAIN, GROUPED, RAGGED, DW])]
    packed = run_batched_ga(rows, CFG)
    alone = [run_batched_ga([r], CFG)[0] for r in rows]
    for a, b in zip(packed, alone):
        assert a.best_obj == b.best_obj
        np.testing.assert_array_equal(a.best_genome, b.best_genome)
    plain = engine._prepare_chunk([rows[1], rows[4]], CFG, HW)
    assert plain.grouped is None and plain.group_dims is None
    mixed = engine._prepare_chunk([rows[1], rows[2]], CFG, HW)
    assert mixed.grouped is not None and mixed.group_dims is None


# -- flexion --------------------------------------------------------------------

@pytest.mark.parametrize("layer", [PLAIN, DW, GROUPED, RAGGED],
                         ids=["plain", "depthwise", "grouped", "ragged"])
@pytest.mark.parametrize("level", ["full", "part"])
def test_flexion_backends_agree_per_kind(layer, level, monkeypatch):
    spec = make_variant("1111", level, hw=HW)
    got = {}
    for backend in ("numpy", "jax"):
        monkeypatch.setenv("REPRO_FLEXION_BACKEND", backend)
        got[backend] = compute_flexion(spec, layer, mc_samples=4000, seed=2)
    assert got["jax"].wf == pytest.approx(got["numpy"].wf, rel=1e-6)
    if layer.kind != "depthwise":
        soft, hard = KINDS.fit_shares(_tile_draws(layer.tile_dims, 2, 4000),
                                      layer.stride, layer.kind == "grouped",
                                      HW.buffer_elems)
        want = hard if level == "part" else soft
        assert got["numpy"].per_axis_wf["T"] == pytest.approx(want, rel=1e-12)


def _tile_draws(dims, seed, n):
    rng = np.random.default_rng(seed)
    return np.stack([rng.integers(1, int(d) + 1, n) for d in dims])


def test_grouped_weights_fill_the_buffer_over_their_groups():
    wide = grouped_gemm("w", 32, 96, 20, 40)
    plain = Layer("p", wide.dims)
    spec = make_variant("1111", hw=HW)
    assert (compute_flexion(spec, wide, 4000, seed=1).wf
            < compute_flexion(spec, plain, 4000, seed=1).wf)


# -- kernel bridge ----------------------------------------------------------------

def test_kernel_bridge_refuses_grouped_and_ragged():
    for layer in (GROUPED, RAGGED, TWIN):
        with pytest.raises(ValueError, match=layer.kind):
            workload_for_layer(layer)
    wl = workload_for_layer(PLAIN)
    assert wl.kind == "matmul" and wl.layer.dims == PLAIN.dims
    with pytest.raises(ValueError):
        workload_for_layer(DW)


# -- the Kimi-K2 decode model ------------------------------------------------------

CONFIG = json.loads((ROOT / "bench" / "configs"
                     / "kimi-k2-decode32k.json").read_text())


def test_kimi_layers_match_the_reference_built_from_published_keys():
    got = get_model("kimi-k2-decode32k")
    want = _load("kimi").layers(CONFIG)
    assert len(got) == len(want) == 62
    for layer, (name, dims, stride, kind, rows) in zip(got, want):
        assert (layer.name, layer.dims, layer.stride, layer.kind,
                layer.group_rows) == (name, tuple(dims), stride, kind,
                                      tuple(rows))
    assert [list(r) for r in KIMI_K2_EXPERT_LOADS] == CONFIG["expert_loads"]


def test_kimi_expert_loads_are_the_recorded_draw_and_drop_no_token():
    for layer, loads in enumerate(KIMI_K2_EXPERT_LOADS, start=1):
        assert loads == expert_loads(layer, 0)
        assert sum(loads) == 8 * 128
        # every device's share: 48 devices x 128 tokens x top-8
        assert sum(sum(expert_loads(layer, d)) for d in range(48)) \
            == 6144 * 8


def test_kimi_runs_through_the_future_proofing_study():
    t = {}
    cfg = GAConfig(population=8, generations=2, pipeline=True)
    norm = future_proofing_study(
        base_model="alexnet", future_models=("kimi-k2-decode32k",),
        class_strs=("1100", "00001"), hw=HW, cfg=cfg,
        include_partflex_1111=False, campaign=True, timings=t,
        flexion={}, wflexion={}, flexion_samples=200)
    assert set(norm["FullFlex1100-alexnet-Opt"]) == {"kimi-k2-decode32k"}
    # 2 variants x 21 distinct rows: 8 grouped, 16 ragged of 8 groups
    assert t["engine.prepare:rows"] == 42
    assert t["engine.prepare:grouped_rows"] == 8
    assert t["engine.prepare:ragged_rows"] == 16
    assert t["engine.prepare:groups"] == 16 * 8
    assert t["engine.prepare.groups"] > 0
    assert t["engine.prepare:chunks"] == 2


def test_tracing_counts_nothing_new_for_plain_rows():
    sink = {}
    with tracing.recording(sink):
        run_batched_ga([engine.EngineRow(PLAIN, SPEC, 1)], CFG)
    assert sink["engine.prepare:grouped_rows"] == 0
    assert sink["engine.prepare:ragged_rows"] == 0
    assert "engine.prepare.groups" not in sink


def test_fixed_config_design_of_kimi_is_one_program_per_variant():
    """The design search buckets a model with ragged layers apart from the
    plain models, so alexnet's design is the one it gets alone."""
    from repro.core import search_fixed_configs
    cfg = GAConfig(population=8, generations=2)
    both = search_fixed_configs(
        [(get_model("alexnet"), FlexSpec(name="a", hw=HW)),
         (get_model("kimi-k2-decode32k"), FlexSpec(name="k", hw=HW))], cfg)
    alone = search_fixed_configs(
        [(get_model("alexnet"), FlexSpec(name="a", hw=HW))], cfg)
    np.testing.assert_array_equal(both[0][0], alone[0][0])
    assert both[0][1].runtime == alone[0][1].runtime
