"""The program's spans and counters (``repro.core.tracing``): what a
recorder counts, that recording changes no result, where the spans land in
a profiler trace, the compile counter, the study's phase keys and the
benchmark readers that consume them, and the stable ``named_scope`` names
inside the device programs."""
import dataclasses
import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (GAConfig, HWConfig, future_proofing_study,
                        make_variant, tracing)
from repro.core import cost_model, engine, flexion_batched, ga_ops
from repro.core.engine import ROW_BUCKET, EngineRow, run_batched_ga
from repro.core.workloads import Layer

ROOT = Path(__file__).resolve().parents[1]
CFG = GAConfig(population=8, generations=4, seed=3)
N_ROWS = ROW_BUCKET + 6          # two chunks, the second one short


def _rows(n=N_ROWS):
    spec = make_variant("1111")
    return [EngineRow(Layer(f"l{i}", (8, 4, 6, 6, 3, 3)), spec, seed=i)
            for i in range(n)]


@pytest.fixture(scope="module")
def recorded():
    sink = {}
    with tracing.recording(sink):
        out = run_batched_ga(_rows(), CFG)
    return sink, out


def test_recorder_counts_live_rows_and_chunks(recorded):
    sink, out = recorded
    assert len(out) == N_ROWS
    assert sink["engine.prepare:rows"] == N_ROWS
    assert sink["engine.prepare:chunks"] == 2
    # every row has a seed of its own, so a stream of its own
    assert sink["engine.prepare.draws:streams"] == N_ROWS
    for name in ("engine.prepare", "engine.prepare.tables",
                 "engine.prepare.draws", "engine.dispatch", "engine.wait",
                 "engine.unpack"):
        assert sink[name] > 0, name


def test_prepare_parts_fit_inside_prepare(recorded):
    sink, _ = recorded
    assert (sink["engine.prepare.tables"] + sink["engine.prepare.draws"]
            <= sink["engine.prepare"])


def test_no_recorder_changes_nothing(recorded):
    sink, out = recorded
    before = dict(sink)
    plain = run_batched_ga(_rows(), CFG)
    assert sink == before            # a closed recording receives nothing
    for a, b in zip(out, plain):
        assert np.array_equal(a.best_genome, b.best_genome)
        assert (a.best_obj, a.history, a.runtime, a.energy, a.feasible) == \
            (b.best_obj, b.history, b.runtime, b.energy, b.feasible)


@pytest.mark.parametrize("n_rows", [1, ROW_BUCKET])
def test_draw_workers_counter(n_rows):
    """``engine.prepare.draws:workers`` counts the threads that drew the
    chunk: the calling thread alone for one row, up to the host's cores
    for a full chunk."""
    sink = {}
    with tracing.recording(sink):
        engine._prepare_chunk(_rows(n_rows), CFG, HWConfig())
    workers = sink["engine.prepare.draws:workers"]
    if n_rows == 1:
        assert workers == 1
    else:
        assert 1 <= workers <= engine._host_cores()
        assert workers == min(n_rows, engine._host_cores(),
                              engine.DRAW_WORKERS)


def test_recorders_nest():
    outer, inner = {}, {}
    with tracing.recording(outer):
        with tracing.span("a", n=2):
            pass
        with tracing.recording(inner):
            with tracing.span("b"):
                pass
    assert outer["a:n"] == 2 and "a" not in inner
    assert outer["b"] == inner["b"] > 0


def test_spans_land_on_the_host_plane(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        run_batched_ga(_rows(), CFG)
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(path))
    prepares, draws = [], []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns, plane.name,
                      line.name)
                if ev.name == "repro.engine.prepare":
                    prepares.append(iv)
                elif ev.name == "repro.engine.prepare.draws":
                    draws.append(iv)
    assert len(prepares) == 2 and len(draws) == 2
    assert all(p[2].startswith("/host") for p in prepares)
    for d in draws:
        assert any(p[0] <= d[0] and d[1] <= p[1] and p[2:] == d[2:]
                   for p in prepares), d


def test_fresh_jit_counts_a_compile():
    sink = {}
    with tracing.recording(sink):
        jax.jit(lambda x: x * 3.0 + 1.0)(np.arange(7.0)).block_until_ready()
    assert sink["jax:compiles"] >= 1
    assert sink["jax:compile_s"] > 0


def _reader(name):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture(scope="module")
def study_timings():
    t = {}
    future_proofing_study(
        base_model="alexnet", future_models=("alexnet", "ncf"),
        class_strs=("1000", "0001"), hw=HWConfig(),
        cfg=dataclasses.replace(CFG, pipeline=True),
        include_partflex_1111=False, campaign=True, timings=t, flexion={},
        wflexion={}, flexion_samples=200)
    return t


def test_study_phase_keys(study_timings):
    t = study_timings
    for phase in ("design_fixed", "replay_frozen", "flexion", "flex_sweep"):
        assert t[phase] == round(t[phase], 6)
        assert t[phase] == pytest.approx(t["study." + phase], abs=2e-6)
    assert t["design.objective:dispatches"] == CFG.generations
    assert t["flexion.draw:samples"] > 0
    assert t["engine.wait"] <= t["flex_sweep"]


@pytest.mark.parametrize("metric", [
    "prepare_us_per_row.campaign", "draws_us_per_row.campaign",
    "wait_share.campaign", "flexion_draw_s.campaign", "design_s.campaign",
    "flexion_s.campaign", "sweep_s.campaign", "stream_share.campaign"])
def test_benchmark_readers_find_the_spans(study_timings, metric):
    value = _reader(metric)({"counters": {"timings": [study_timings]}})
    assert value is not None and math.isfinite(value)
    if metric in ("wait_share.campaign", "stream_share.campaign"):
        assert 0.0 <= value <= 100.0
    if metric == "stream_share.campaign":
        # the sweep's 2 specs x 2 models read each R-pinned seed's stream
        assert value < 100.0


def test_ga_program_scopes():
    hw = HWConfig()
    c = engine._prepare_chunk(_rows(2), CFG, hw)
    text = engine._ga_program.lower(
        c.dims, c.stride, c.depthwise, c.tile_lo, c.tile_hi,
        c.hard_partition, c.table_id, c.orders, c.pairs, c.shapes, c.reprs,
        c.lens, c.pop0, c.draws, np.int32(c.gens), hw=hw,
        n_elite=ga_ops.n_elite(CFG),
        objective=CFG.objective).as_text(debug_info=True)
    for scope in ("evaluate", "select", "breed"):
        assert f"/{scope}/" in text, scope


def test_evaluate_rows_and_flexion_scopes():
    hw = HWConfig()
    ones = jnp.ones((2, 6), jnp.int32)
    text = cost_model.evaluate_rows.lower(
        ones, jnp.ones(2, jnp.int32), jnp.zeros(2, bool), ones,
        jnp.zeros((2, 6), jnp.int32), jnp.asarray([[0, 1]] * 2),
        jnp.ones((2, 2), jnp.int32), jnp.zeros(2, bool),
        hw=hw).as_text(debug_info=True)
    assert "/evaluate_rows/" in text
    t = np.ones((1, 6, 4), np.float32)
    one = np.ones(1, np.float32)
    text = flexion_batched._jax_eval().lower(
        t, one, np.zeros(1, bool), one).as_text(debug_info=True)
    assert "/flexion_fractions/" in text
