"""Property tests for MapSpace legality invariants (hypothesis), plus
seeded non-property variants of the pinned-gene contract at the bottom.
"""
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro.core import (FULLFLEX, GAConfig, INFLEX, PARTFLEX, Layer,
                        MapSpace, inflex_baseline, make_variant)
from repro.core import ga_ops
from repro.core.mapper import _Operators

LAYER = Layer("t", (64, 32, 28, 28, 3, 3))

SPECS = {
    "inflex": inflex_baseline(),
    "partflex": make_variant("1111", PARTFLEX),
    "fullflex": make_variant("1111", FULLFLEX),
}


@given(st.integers(0, 2**31 - 1), st.sampled_from(sorted(SPECS)))
@settings(max_examples=30, deadline=None)
def test_clip_of_sample_is_identity(seed, flex):
    """Sampled genomes are already legal: clip(sample(...)) == sample(...)."""
    space = MapSpace(LAYER, SPECS[flex])
    g = space.sample(np.random.default_rng(seed), 16)
    assert (space.clip(g) == g).all()


@given(st.integers(0, 2**31 - 1), st.sampled_from(sorted(SPECS)))
@settings(max_examples=30, deadline=None)
def test_clip_is_idempotent(seed, flex):
    """clip is a projection: clip(clip(x)) == clip(x) for arbitrary ints."""
    space = MapSpace(LAYER, SPECS[flex])
    rng = np.random.default_rng(seed)
    g = rng.integers(-1000, 1000, size=(32, space.GENOME_LEN))
    c = space.clip(g)
    assert (space.clip(c) == c).all()
    assert (c[:, 0:6] >= space.tile_lo).all()
    assert (c[:, 0:6] <= space.tile_hi).all()


@given(st.integers(0, 2**31 - 1), st.sampled_from(sorted(SPECS)))
@settings(max_examples=30, deadline=None)
def test_decoded_tiles_divide_or_clip_into_layer_dims(seed, flex):
    """Decoded tile sizes always land in [1, dim] — the cost model's
    divide-or-clip contract."""
    space = MapSpace(LAYER, SPECS[flex])
    rng = np.random.default_rng(seed)
    g = space.clip(rng.integers(-500, 500, size=(32, space.GENOME_LEN)))
    tiles, orders, pairs, shapes, reprs = space.decode_batch(g)
    assert (tiles >= 1).all()
    assert (tiles <= np.asarray(LAYER.dims)).all()
    # index genes decode into their tables
    legal_orders = {tuple(r) for r in space.order_table}
    assert all(tuple(o) in legal_orders for o in orders)
    assert (shapes.prod(axis=1) <= space.spec.hw.num_pes).all()
    assert np.isin(reprs, space.repr_table).all()


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_pinned_genes_never_mutate_hypothesis(seed):
    _check_pinned_genes_never_mutate(seed)


def _check_pinned_genes_never_mutate(seed):
    """InFlex pins every axis: neither the numpy ``_Operators.mutate`` nor
    the batched engine's JAX mutate may move any gene."""
    spec = inflex_baseline()
    assert spec.class_str() == "00000"
    space = MapSpace(LAYER, spec)
    cfg = GAConfig(population=16, generations=4, seed=seed)
    rng = np.random.default_rng(seed)
    g = space.sample(rng, 16)

    ops = _Operators(space, cfg, np.random.default_rng(seed + 1))
    assert (ops.mutate(g) == g).all()

    draws = ga_ops.gen_slice(
        ga_ops.draw_run(np.random.default_rng(seed + 2), space, cfg,
                        gens=1, n=16), 0)
    jax_mutated = np.asarray(ga_ops.apply_mutation(
        jnp.asarray(g), draws, jnp.asarray(space.tile_lo),
        jnp.asarray(space.tile_hi), jnp.asarray(space.table_lens()), jnp))
    assert (jax_mutated == g).all()


def test_pinned_genes_never_mutate():
    # seeded variant over a fixed sweep
    for seed in (0, 7, 123):
        _check_pinned_genes_never_mutate(seed)


def test_partially_pinned_axes_stay_pinned():
    """PartFlex-0100 pins T/P/S but opens O: only the order gene may move."""
    spec = make_variant("0100", PARTFLEX)
    space = MapSpace(LAYER, spec)
    cfg = GAConfig(population=32, generations=4, seed=5)
    rng = np.random.default_rng(5)
    g = space.sample(rng, 32)
    mutated = _Operators(space, cfg, rng).mutate(g)
    assert (mutated[:, 0:6] == g[:, 0:6]).all()     # tiles pinned
    assert (mutated[:, 7:10] == g[:, 7:10]).all()   # pair/shape/repr pinned
    assert (mutated[:, 6] < len(space.order_table)).all()


def test_numpy_and_jax_mutate_agree_bitwise():
    """The same draws applied through numpy and jax.numpy produce identical
    genomes (the golden-parity cornerstone)."""
    spec = make_variant("1111", FULLFLEX)
    space = MapSpace(LAYER, spec)
    cfg = GAConfig(population=32, generations=4, seed=9)
    rng = np.random.default_rng(9)
    g = space.sample(rng, 32)
    d = ga_ops.gen_slice(ga_ops.draw_run(rng, space, cfg, 1, 32), 0)
    args = (space.tile_lo, space.tile_hi, space.table_lens())
    via_np = ga_ops.apply_mutation(g, d, *args, np)
    via_jax = np.asarray(ga_ops.apply_mutation(
        jnp.asarray(g), d, *(jnp.asarray(a) for a in args), jnp))
    assert (via_np == via_jax).all()
    via_np_x = ga_ops.apply_crossover(g, d, np)
    via_jax_x = np.asarray(ga_ops.apply_crossover(jnp.asarray(g), d, jnp))
    assert (via_np_x == via_jax_x).all()
