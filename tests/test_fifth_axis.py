"""Fifth-axis (representation) tests: 5-axis flexion properties over the
2^5 class domain plus the R-pinned golden-parity discipline (ISSUE 6).

The load-bearing invariant: with R pinned to the native width, the 10-gene
engine must reproduce the v4 9-gene results bit-identically — pinned-R runs
draw no R randomness (byte-identical Generator streams) and execute the
pre-R cost program (identical XLA fusion).  The committed-anchor form of
that invariant lives in test_golden_metrics.py; here we pin the mechanics.
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (FULLFLEX, GAConfig, INFLEX, PARTFLEX, Layer,
                        MapSpace, RepresentationSpec, compute_flexion,
                        evaluate_fixed_genome, get_model, make_variant,
                        search)
from repro.core.classes import ALL_CLASSES_5, class_str
from repro.core.precision import FULL_BITS, PART_BITS
from repro.core.spec import FlexSpec, HWConfig

from _reference_ga import search_layer

LAYER = Layer("t", (64, 32, 28, 28, 3, 3))

# one common C_X scale for all 32 classes: the 5-axis FullFlex accelerator
REF5 = make_variant("11111", FULLFLEX)


def test_all_classes_5_taxonomy():
    assert len(ALL_CLASSES_5) == 32
    assert ALL_CLASSES_5[0] == "00000" and ALL_CLASSES_5[-1] == "11111"
    assert class_str(0b10101, 5) == "10101"


def test_repr_spec_tables():
    hw = HWConfig()
    native = 8 * hw.bytes_per_elem
    assert RepresentationSpec(flex=INFLEX).bits_table(native).tolist() == [
        native]
    assert RepresentationSpec(flex=INFLEX, fixed_bits=4).bits_table(
        native).tolist() == [4]
    assert RepresentationSpec(flex=PARTFLEX).bits_table(
        native).tolist() == sorted(set(PART_BITS))
    assert RepresentationSpec(flex=FULLFLEX).bits_table(
        native).tolist() == sorted(set(FULL_BITS))


# ---- 5-axis flexion properties over the 2^5 class domain -------------------

@given(st.integers(0, 31), st.sampled_from([PARTFLEX, FULLFLEX]))
@settings(max_examples=16, deadline=None)
def test_flexion_bounds_and_product_over_32_classes(cid, level):
    cs = class_str(cid, 5)
    spec = make_variant(cs, level) if cid else inflex5()
    f = compute_flexion(spec, LAYER, mc_samples=4_000, reference=REF5)
    assert 0.0 <= f.hf <= 1.0 + 1e-9
    assert 0.0 <= f.wf <= 1.0 + 1e-9
    assert set(f.per_axis_hf) == {"T", "O", "P", "S", "R"}
    for v in list(f.per_axis_hf.values()) + list(f.per_axis_wf.values()):
        assert 0.0 <= v <= 1.0 + 1e-9
    # per-axis fractions multiply (the axes are a cross product)
    assert f.hf == pytest.approx(np.prod(list(f.per_axis_hf.values())),
                                 rel=1e-9)
    assert f.wf == pytest.approx(np.prod(list(f.per_axis_wf.values())),
                                 rel=1e-9)


def inflex5():
    from repro.core import inflex_baseline
    return inflex_baseline()


@given(st.integers(1, 31))
@settings(max_examples=16, deadline=None)
def test_exact_axes_monotone_in_flex_level(cid):
    """On the exactly-counted axes (O/P/S/R), INFLEX <= PARTFLEX <= FULLFLEX
    per class — deterministic table counts, no MC tolerance needed."""
    cs = class_str(cid, 5)
    f_part = compute_flexion(make_variant(cs, PARTFLEX), LAYER,
                             mc_samples=1_000, reference=REF5)
    f_full = compute_flexion(make_variant(cs, FULLFLEX), LAYER,
                             mc_samples=1_000, reference=REF5)
    f_in = compute_flexion(inflex5(), LAYER, mc_samples=1_000,
                           reference=REF5)
    for ax in ("O", "P", "S", "R"):
        assert f_in.per_axis_hf[ax] <= f_part.per_axis_hf[ax]
        assert f_part.per_axis_hf[ax] <= f_full.per_axis_hf[ax] + 1e-12


def test_r_axis_fractions_are_exact_counts():
    """|A_R|/|C_R| against the FullFlex-5 reference: 1/5 pinned, 3/5
    PartFlex, 5/5 FullFlex (the bit-width menu is a small exact table)."""
    n_full = len(set(FULL_BITS))
    pinned = compute_flexion(make_variant("1111"), LAYER, mc_samples=1_000,
                             reference=REF5)
    part = compute_flexion(make_variant("11111", PARTFLEX), LAYER,
                           mc_samples=1_000, reference=REF5)
    full = compute_flexion(make_variant("11111", FULLFLEX), LAYER,
                           mc_samples=1_000, reference=REF5)
    assert pinned.per_axis_hf["R"] == 1.0 / n_full
    assert part.per_axis_hf["R"] == len(set(PART_BITS)) / n_full
    assert full.per_axis_hf["R"] == 1.0


def test_rpinned_default_reference_preserves_v4_values():
    """The default reference is R-adaptive: a pinned-R spec is measured
    against a pinned-R FullFlex-T/O/P/S reference, so its R term is exactly
    1.0 and the 4-axis H-F equals the v4 value (FullFlex-1111 == 1)."""
    f = compute_flexion(make_variant("1111", FULLFLEX), LAYER,
                        mc_samples=4_000)
    assert f.per_axis_hf["R"] == 1.0
    assert f.hf == pytest.approx(1.0)
    # and an R-open spec is measured against the FullFlex-R domain
    f5 = compute_flexion(make_variant("11111", FULLFLEX), LAYER,
                         mc_samples=4_000)
    assert f5.per_axis_hf["R"] == 1.0
    assert f5.hf == pytest.approx(1.0)


# ---- R-pinned golden-parity mechanics --------------------------------------

def test_rpinned_space_draws_no_r_randomness():
    """A pinned-R map space consumes the byte-identical numpy Generator
    stream of the v4 9-gene sampler: the same seed must yield the same
    legacy genes, with gene 9 inert at 0."""
    space = MapSpace(LAYER, make_variant("1111"))
    g10 = space.sample(np.random.default_rng(123), 32)
    # re-draw the v4 stream by hand: one bulk (n, 9) uniform draw
    rng = np.random.default_rng(123)
    u = rng.random((32, 9))
    lo = np.concatenate([space.tile_lo, np.zeros(3, np.int64)])
    span = np.concatenate([
        (space.tile_hi - space.tile_lo + 1).astype(np.int64),
        space.table_lens().astype(np.int64)[:3]])
    legacy = (lo + u * span).astype(np.int32)
    assert (g10[:, :9] == legacy).all()
    assert (g10[:, 9] == 0).all()


def test_rpinned_serial_batched_bit_parity():
    """The engine equals the reference GA (tests/_reference_ga.py) with R
    pinned and with R open."""
    cfg = GAConfig(population=16, generations=4, seed=0)
    for cs in ("1111", "11111"):
        rs = search_layer(LAYER, make_variant(cs), cfg)
        rb = search(LAYER, make_variant(cs), cfg)
        assert rs.mapping == rb.mapping
        assert rs.runtime == rb.runtime
        assert rs.energy == rb.energy
        assert rs.history == rb.history


def test_ropen_search_exploits_narrow_widths():
    """Opening R can only help: the 5-axis FullFlex search on the same seed
    must find a runtime no worse than the R-pinned one (narrow operands buy
    bandwidth and subword throughput in the cost model)."""
    cfg = GAConfig(population=32, generations=8, seed=0)
    pinned = search(LAYER, make_variant("1111"), cfg)
    ropen = search(LAYER, make_variant("11111"), cfg)
    assert ropen.runtime <= pinned.runtime * 1.001
    assert ropen.mapping.repr_bits in FULL_BITS


def test_frozen_spec_pins_searched_width():
    """freeze_spec_from_genome pins R to the decoded width; replaying the
    frozen spec keeps that width in the mapping."""
    from repro.core.dse import freeze_spec_from_genome
    layers = get_model("ncf")
    probe = FlexSpec(name="probe", hw=HWConfig())
    genome = np.asarray([8, 4, 1, 1, 1, 1, 3, 5, 7, 0], np.int32)
    frozen = freeze_spec_from_genome(probe, layers, genome, name="frz")
    assert frozen.class_str() == "00000"
    assert frozen.representation.fixed_bits == 8
    replay = evaluate_fixed_genome(layers, frozen, genome)
    assert all(r.mapping.repr_bits == 8 for r in replay.per_layer)
