"""The two stages of a row's draws (``repro.core.ga_ops``): the stream
stage (``draw_stream``) makes every Generator call and depends on the map
space only through ``r_open``; the row stage (``row_draws``) applies a
space.  Together they equal ``initial_population`` then ``draw_run`` on
one Generator, which is what lets the engine draw a stream once for all
the rows that read it."""
import numpy as np
import pytest

from repro.core import (FULLFLEX, GAConfig, PARTFLEX, Layer, MapSpace,
                        inflex_baseline, make_variant)
from repro.core import ga_ops

LAYERS = {
    "conv": Layer("conv", (64, 32, 28, 28, 3, 3)),
    "depthwise": Layer("dw", (1, 480, 14, 14, 5, 5), depthwise=True),
    "unit-dims": Layer("gemm", (512, 256, 1, 1, 1, 1)),
}

# pinned and open T/O/P/S/R axes
SPECS = {
    "inflex": inflex_baseline(),
    "partflex-1111": make_variant("1111", PARTFLEX),
    "fullflex-1111": make_variant("1111", FULLFLEX),
    "fullflex-0101": make_variant("0101", FULLFLEX),
    "fullflex-11111": make_variant("11111", FULLFLEX),
    "fullflex-00001": make_variant("00001", FULLFLEX),
}

BUDGETS = [(16, 6), (7, 3), (100, 2)]     # (population, generations)


def _per_generator(seed, space, cfg):
    rng = np.random.default_rng(seed)
    pop = ga_ops.initial_population(rng, space, cfg)
    n = cfg.population - ga_ops.n_elite(cfg)
    return pop, ga_ops.draw_run(rng, space, cfg, cfg.generations, n)


def _assert_same(got, want):
    (pop_a, draws_a), (pop_b, draws_b) = got, want
    assert pop_a.dtype == pop_b.dtype and np.array_equal(pop_a, pop_b)
    for field in ga_ops.GenDraws._fields:
        a, b = getattr(draws_a, field), getattr(draws_b, field)
        assert a.dtype == b.dtype and np.array_equal(a, b), field


@pytest.mark.parametrize("layer", sorted(LAYERS))
@pytest.mark.parametrize("spec", sorted(SPECS))
def test_stream_then_row_stage_equals_one_generator(spec, layer):
    space = MapSpace(LAYERS[layer], SPECS[spec])
    open_r = ga_ops.r_open(space.table_lens())
    assert open_r == spec.endswith(("11111", "00001"))
    for population, gens in BUDGETS:
        cfg = GAConfig(population=population, generations=gens)
        n = population - ga_ops.n_elite(cfg)
        for seed in (0, 7, 2**31 + 5):
            stream = ga_ops.draw_stream(seed, open_r, cfg, gens, n)
            _assert_same(ga_ops.row_draws(stream, space),
                         _per_generator(seed, space, cfg))


@pytest.mark.parametrize("r_open", [False, True])
def test_one_stream_serves_every_space_with_its_r_test(r_open):
    """One stream, read by spaces of other layers and specs in turn (its
    kept columns included), gives each the draws of its own Generator."""
    cfg = GAConfig(population=12, generations=4)
    n = cfg.population - ga_ops.n_elite(cfg)
    stream = ga_ops.draw_stream(1234, r_open, cfg, cfg.generations, n)
    spaces = [MapSpace(layer, spec) for spec in SPECS.values()
              for layer in LAYERS.values()]
    spaces = [s for s in spaces
              if ga_ops.r_open(s.table_lens()) == r_open]
    assert len(spaces) >= 6
    for space in spaces + spaces[::-1]:
        _assert_same(ga_ops.row_draws(stream, space),
                     _per_generator(1234, space, cfg))


def test_row_stage_refuses_a_stream_of_the_other_r_test():
    cfg = GAConfig(population=8, generations=2)
    space = MapSpace(LAYERS["conv"], SPECS["fullflex-11111"])
    stream = ga_ops.draw_stream(3, False, cfg, 2, 7)
    with pytest.raises(AssertionError, match="R test"):
        ga_ops.row_draws(stream, space)


def test_empty_draw_stack_leaves_only_the_live_block_unwritten():
    stack = ga_ops.empty_draw_stack(8, 5, 3, gens=6, live=2)
    inert = ga_ops.empty_draw_stack(8, 5, 3)
    for a, b in zip(stack, inert):
        assert np.array_equal(a[6:], b[6:])
        assert np.array_equal(a[:, 2:], b[:, 2:])
    assert (inert.step == 1).all() and (inert.dv == 1).all()
    assert not inert.m_tile.any() and (inert.stepdir == 1).all()

