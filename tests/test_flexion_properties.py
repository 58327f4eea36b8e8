"""Property tests for the flexion metric (paper Table 1 / Fig 5).

Checked properties:
  * H-F and W-F (and every per-axis fraction) live in [0, 1];
  * the reported products equal the product of the per-axis fractions;
  * opening an axis (InFlex -> PartFlex -> FullFlex) never decreases
    flexion — A_X only grows;
  * the Monte-Carlo T-axis estimate converges: error against a large-sample
    reference shrinks as the sample count grows.

Hypothesis drives the spec/layer domain via the optional-dep shim (the
domain is finite and the MC seed fixed, so examples are deterministic).
"""
import dataclasses
import itertools

import numpy as np
import pytest

from repro.core import (FULLFLEX, INFLEX, PARTFLEX, compute_flexion,
                        make_variant, model_flexion)
from repro.core.workloads import Layer

from hypothesis import given, settings
from hypothesis import strategies as st

LAYERS = [Layer("conv", (64, 32, 28, 28, 3, 3)),
          Layer("dw", (1, 480, 14, 14, 5, 5), depthwise=True),
          Layer("gemm", (256, 64, 128, 1, 1, 1))]
CLASS_STRS = ["".join(b) for b in itertools.product("01", repeat=4)]
AXIS_FIELDS = ("tile", "order", "parallel", "shape")
MC = 4000          # fixed seed + fixed count => deterministic estimates


def _with_axis(spec, axis: int, flex: str):
    field = AXIS_FIELDS[axis]
    return dataclasses.replace(
        spec, **{field: dataclasses.replace(getattr(spec, field),
                                            flex=flex)})


@settings(max_examples=24, deadline=None)
@given(cs=st.sampled_from(CLASS_STRS),
       level=st.sampled_from([PARTFLEX, FULLFLEX]),
       li=st.integers(min_value=0, max_value=len(LAYERS) - 1))
def test_fractions_bounded_and_multiply(cs, level, li):
    rep = compute_flexion(make_variant(cs, level), LAYERS[li],
                          mc_samples=MC, seed=0)
    for frac in (rep.hf, rep.wf, *rep.per_axis_hf.values(),
                 *rep.per_axis_wf.values()):
        assert 0.0 <= frac <= 1.0
    assert rep.hf == float(np.prod(list(rep.per_axis_hf.values())))
    assert rep.wf == float(np.prod(list(rep.per_axis_wf.values())))
    assert rep.mc_samples == MC


@settings(max_examples=24, deadline=None)
@given(cs=st.sampled_from(CLASS_STRS),
       axis=st.integers(min_value=0, max_value=3),
       li=st.integers(min_value=0, max_value=len(LAYERS) - 1))
def test_opening_axis_never_decreases_flexion(cs, axis, li):
    """InFlex -> PartFlex -> FullFlex on any one axis, any surrounding
    class: |A_X| only grows, so H-F and W-F are monotone.  The other axes'
    fractions are identical across the three specs (same MC seed and draw
    order), so the product comparison is exact."""
    base = make_variant(cs, FULLFLEX)
    reps = [compute_flexion(_with_axis(base, axis, lv), LAYERS[li],
                            mc_samples=MC, seed=0)
            for lv in (INFLEX, PARTFLEX, FULLFLEX)]
    assert reps[0].hf <= reps[1].hf <= reps[2].hf
    assert reps[0].wf <= reps[1].wf <= reps[2].wf


def test_mc_error_shrinks_with_sample_count():
    """Binomial convergence of the T-axis estimate: 64x the samples must
    beat the small-sample worst case against a 200K-sample reference
    (expected ~8x shrink; asserted at >2x for slack)."""
    spec = make_variant("1000", PARTFLEX)
    layer = LAYERS[0]
    ref = compute_flexion(spec, layer, mc_samples=200_000, seed=123).wf
    err = {n: max(abs(compute_flexion(spec, layer, mc_samples=n,
                                      seed=s).wf - ref)
                  for s in range(5))
           for n in (400, 25_600)}
    assert err[25_600] < err[400] / 2.0
    assert err[25_600] < ref                 # estimate is in the right ballpark


def test_model_hf_is_layer_count_invariant():
    """H-F is workload-agnostic: the shared (hw, hard, n, seed) reference
    cache makes model_flexion report the SAME H-F no matter how many layers
    the model has — the old per-layer ``seed + i`` resampling drifted."""
    spec = make_variant("1000", PARTFLEX)
    one = model_flexion(spec, LAYERS[:1], mc_samples=MC, seed=0)
    full = model_flexion(spec, LAYERS, mc_samples=MC, seed=0)
    solo = compute_flexion(spec, mc_samples=MC, seed=0)
    assert one.hf == full.hf == solo.hf
    assert one.per_axis_hf == full.per_axis_hf == solo.per_axis_hf
    # sanity-bound the value (the paper quotes ~0.22 at 1:1:1 with the full
    # 200K budget; the exact literal is left to BENCH_mapper.json, which
    # has a documented re-anchor flow if a numpy release moves the stream)
    assert 0.2 < one.per_axis_hf["T"] < 0.8


def test_model_flexion_empty_model_raises():
    with pytest.raises(ValueError, match="no layers"):
        model_flexion(make_variant("1111"), [])


def test_inflex_everywhere_is_minimal():
    """The fully inflexible accelerator has (near-)zero flexion — strictly
    less than any single-axis FullFlex variant on the same layer."""
    layer = LAYERS[0]
    base = compute_flexion(make_variant("0000"), layer, mc_samples=MC,
                           seed=0)
    for cs in ("1000", "0100", "0010", "0001"):
        rep = compute_flexion(make_variant(cs, FULLFLEX), layer,
                              mc_samples=MC, seed=0)
        assert base.hf <= rep.hf
        assert base.wf <= rep.wf
    assert base.hf == pytest.approx(0.0, abs=1e-6)
