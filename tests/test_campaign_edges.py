"""Campaign edge-case regression tests (ISSUE 5 satellites).

Each test here pins a bug that existed before this change:

  * empty campaigns crashed on the engine's row assert instead of
    returning ``[]``;
  * degenerate ``GAConfig``s (``generations=0``, ``elite_frac >= 1``, tiny
    populations) were accepted and then returned garbage (an assert-crash
    or an inf-objective row);
  * an exception while preparing/dispatching chunk i+1 in the pipelined
    engine loop silently abandoned the already-dispatched in-flight chunk.

It also holds the one search path to what the removed forks computed:
``run_dse`` over candidates on several HWConfigs, and the fig13 study with
and without its campaign batching.
"""
import dataclasses

import pytest

from repro.core import (GAConfig, HWConfig, future_proofing_study, get_model,
                        inflex_baseline, make_variant, run_batched_ga,
                        run_dse, search_campaign, search_model)
from repro.core import engine as engine_mod
from repro.core import mapper as mapper_mod
from repro.core.engine import EngineRow, ROW_BUCKET

LAYERS = get_model("ncf")
CFG = GAConfig(population=6, generations=2, seed=5)


# --------------------------------------------------------------------------
# empty campaigns return empty results
# --------------------------------------------------------------------------

def test_empty_campaigns_return_empty():
    assert run_batched_ga([], CFG) == []
    assert search_campaign([], CFG) == []
    assert run_dse(LAYERS, [], CFG) == []
    assert run_dse(LAYERS, [], CFG, with_flexion=True) == []


def test_empty_request_inside_campaign_is_fine():
    """A request with no layers yields an empty (zero-cost) ModelResult,
    not a crash."""
    out = search_campaign([([], inflex_baseline()),
                           (LAYERS, inflex_baseline())], CFG)
    assert len(out) == 2
    assert out[0].per_layer == [] and out[0].runtime == 0.0
    assert out[1].per_layer and out[1].runtime > 0.0


# --------------------------------------------------------------------------
# degenerate GAConfigs are rejected at construction
# --------------------------------------------------------------------------

@pytest.mark.parametrize("how", ["construct", "replace"])
@pytest.mark.parametrize("bad", [
    dict(generations=0), dict(generations=-3),
    dict(population=1), dict(population=0),
    dict(elite_frac=1.0), dict(elite_frac=1.5), dict(elite_frac=-0.1),
    dict(mutation_rate=1.0001), dict(mutation_rate=-0.5),
    dict(crossover_rate=2.0), dict(crossover_rate=-1.0),
])
def test_degenerate_gaconfigs_rejected(how, bad):
    """Construction and dataclasses.replace (which re-runs __post_init__)
    must both raise — the old behavior let ``generations=0`` through and
    the search then returned garbage."""
    with pytest.raises(ValueError):
        if how == "construct":
            GAConfig(**bad)
        else:
            dataclasses.replace(GAConfig(), **bad)


def test_boundary_gaconfigs_accepted():
    # the smallest legal GA: 1 elite + 1 child, one generation
    GAConfig(population=2, generations=1, elite_frac=0.0,
             mutation_rate=0.0, crossover_rate=1.0)
    GAConfig(elite_frac=0.99, mutation_rate=1.0, crossover_rate=0.0)


# --------------------------------------------------------------------------
# pipelined engine loop: a poisoned chunk must not abandon in-flight work
# --------------------------------------------------------------------------

def test_pipeline_poisoned_chunk_collects_in_flight_and_names_chunk(
        monkeypatch):
    """Rows 0..63 form a good chunk; row 64 poisons chunk 1's preparation
    (a negative seed makes ``np.random.default_rng`` raise).  The pipelined
    loop must first collect the already-dispatched chunk 0 (never leave
    device work orphaned) and then surface the error with the failing
    chunk's context."""
    spec = make_variant("1111")
    good = [EngineRow(layer, spec, seed=1000 * i)
            for i, layer in enumerate(
                (get_model("mnasnet") + get_model("resnet50"))[:ROW_BUCKET])]
    poisoned = good + [EngineRow(LAYERS[0], spec, seed=-1)]

    collected = []
    real_collect = engine_mod._collect_chunk

    def counting_collect(n_rows, gens, outputs):
        out = real_collect(n_rows, gens, outputs)
        collected.append(n_rows)
        return out

    monkeypatch.setattr(engine_mod, "_collect_chunk", counting_collect)
    cfg = dataclasses.replace(CFG, population=4, pipeline=True)
    with pytest.raises(RuntimeError, match=r"chunk 1/2") as exc:
        run_batched_ga(poisoned, cfg)
    assert isinstance(exc.value.__cause__, ValueError)   # the real poison
    assert collected == [ROW_BUCKET], \
        "the dispatched in-flight chunk was not collected before re-raise"

    # sanity: the same rows minus the poison complete normally
    collected.clear()
    assert len(run_batched_ga(good, cfg)) == ROW_BUCKET
    assert collected == [ROW_BUCKET]


# --------------------------------------------------------------------------
# the one search path: mixed HWConfigs and the study's batching
# --------------------------------------------------------------------------

def test_run_dse_mixed_hw_matches_per_spec(monkeypatch):
    """Candidates on two HWConfigs go through one campaign per HWConfig and
    come back in candidate order, each equal to its own ``search_model``."""
    small = HWConfig(num_pes=16, dram_bw=2.0)
    specs = [inflex_baseline(), make_variant("1111", hw=small),
             make_variant("1111"), make_variant("0101", hw=small)]
    calls = []
    real = mapper_mod.run_batched_ga

    def counting(rows, cfg, row_cache=None):
        calls.append({r.spec.hw for r in rows})
        return real(rows, cfg, row_cache=row_cache)

    with monkeypatch.context() as m:
        m.setattr(mapper_mod, "run_batched_ga", counting)
        rows = run_dse(LAYERS, specs, CFG)
    assert calls == [{HWConfig()}, {small}]
    assert [r.spec_name for r in rows] == [s.name for s in specs]
    for spec, r in zip(specs, rows):
        solo = search_model(LAYERS, spec, CFG)
        assert r.runtime == solo.runtime and r.energy == solo.energy
        for a, b in zip(r.model_result.per_layer, solo.per_layer):
            assert a.mapping == b.mapping and a.history == b.history
    assert rows[1].runtime != rows[2].runtime   # the HWConfig mattered


def test_study_table_same_with_and_without_campaign():
    """The fig13 study's table and cells agree exactly whether its phases
    run as cross-model campaigns or model by model."""
    cfg = GAConfig(population=6, generations=2, seed=3)
    runs = []
    for campaign in (True, False):
        results = {}
        table = future_proofing_study(
            base_model="ncf", future_models=("ncf", "dlrm"),
            class_strs=("1111", "0101"), cfg=cfg, campaign=campaign,
            results=results)
        runs.append((table, {k: (spec.name, res.runtime, res.energy,
                                 [r.mapping for r in res.per_layer])
                             for k, (spec, res) in results.items()}))
    assert runs[0] == runs[1]
