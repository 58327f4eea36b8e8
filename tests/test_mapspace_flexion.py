"""Map-space, taxonomy and flexion tests (paper Secs 3-4)."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (FULLFLEX, INFLEX, PARTFLEX, FlexSpec, HWConfig,
                        Layer, MapSpace, compute_flexion, inflex_baseline,
                        make_variant, workload_space_size)
from repro.core.classes import ALL_CLASSES, PRIOR_WORK, class_id, class_str


def test_sixteen_classes():
    assert len(ALL_CLASSES) == 16
    assert ALL_CLASSES[0] == "0000" and ALL_CLASSES[15] == "1111"


def test_class_vector_roundtrip():
    for cid in range(16):
        vec = tuple(int(b) for b in class_str(cid))
        assert class_id(vec) == cid


def test_variant_class_strings():
    # 4-char class strings pin R (trailing 0 in the 5-axis string)
    for cs in ("0000", "1000", "0101", "1111"):
        assert make_variant(cs).class_str() == cs + "0"
        if cs != "0000":
            assert make_variant(cs, PARTFLEX).class_str() == cs + "0"
    # 5-char class strings drive the R axis directly
    for cs in ("00001", "10101", "11111"):
        assert make_variant(cs).class_str() == cs


def test_prior_work_classified():
    assert PRIOR_WORK["NVDLA"] == (0, 0, 0, 0)
    assert PRIOR_WORK["MAERI"] == (1, 1, 1, 1)


LAYER = Layer("t", (64, 32, 28, 28, 3, 3))


def test_mapspace_cardinalities():
    full = MapSpace(LAYER, make_variant("1111"))
    c = full.axis_cardinalities()
    assert c["O"] == 720 and c["P"] == 30
    assert c["T"] == 64 * 32 * 28 * 28 * 3 * 3
    inflex = MapSpace(LAYER, inflex_baseline())
    ci = inflex.axis_cardinalities()
    assert ci["O"] == 1 and ci["P"] == 1 and ci["S"] == 1 and ci["T"] == 1


def test_genome_encode_decode_roundtrip():
    space = MapSpace(LAYER, make_variant("1111"))
    rng = np.random.default_rng(0)
    g = space.sample(rng, 16)
    for i in range(16):
        m = space.decode(g[i])
        g2 = space.encode(m)
        assert space.decode(g2) == m


def test_clip_respects_pinned_axes():
    space = MapSpace(LAYER, inflex_baseline())
    rng = np.random.default_rng(0)
    g = rng.integers(0, 1000, size=(32, space.GENOME_LEN)).astype(np.int64)
    c = space.clip(g)
    fixed = np.minimum((64, 16, 3, 3, 3, 3), space.dims)
    assert (c[:, 0:6] == fixed).all()
    assert (c[:, 6] == 0).all() and (c[:, 7] == 0).all() \
        and (c[:, 8] == 0).all() and (c[:, 9] == 0).all()


# ---- flexion ---------------------------------------------------------------

def test_flexion_bounds_and_monotonicity():
    layer = LAYER
    f_in = compute_flexion(inflex_baseline(), layer, mc_samples=20_000)
    f_part = compute_flexion(make_variant("1111", PARTFLEX), layer,
                             mc_samples=20_000)
    f_full = compute_flexion(make_variant("1111", FULLFLEX), layer,
                             mc_samples=20_000)
    for f in (f_in, f_part, f_full):
        assert 0.0 <= f.hf <= 1.0 + 1e-9
        assert 0.0 <= f.wf <= 1.0 + 1e-9
    assert f_in.hf <= f_part.hf <= f_full.hf + 1e-9
    assert f_in.wf <= f_part.wf <= f_full.wf + 1e-9
    assert f_full.hf == pytest.approx(1.0)


def test_hard_partition_flexion_below_one():
    """PartFlex-1000 1:1:1 partition: H-F(T) strictly within (0,1) — the
    paper quotes ~0.22."""
    f = compute_flexion(make_variant("1000", PARTFLEX), LAYER,
                        mc_samples=50_000)
    assert 0.05 < f.per_axis_hf["T"] < 0.8


def test_workload_space_is_huge():
    # the paper quotes O(10^24) map spaces for full models
    assert workload_space_size(Layer("l", (256, 256, 56, 56, 3, 3))) > 1e15


@given(st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_sampled_genomes_always_legal(seed):
    spec = make_variant("1111")
    space = MapSpace(LAYER, spec)
    rng = np.random.default_rng(seed)
    g = space.sample(rng, 8)
    assert (g[:, 0:6] >= 1).all()
    assert (g[:, 0:6] <= space.dims).all()
    assert (g[:, 6] < len(space.order_table)).all()
    assert (g[:, 7] < len(space.pair_table)).all()
    assert (g[:, 8] < len(space.shape_table)).all()
    assert (g[:, 9] < len(space.repr_table)).all()
