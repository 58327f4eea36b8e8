"""Cost-model unit + property tests (hypothesis): physical invariants."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import HWConfig, lower_bound_cycles
from repro.core.cost_model import evaluate_mapping
from repro.core.spec import order_str_to_perm

HW = HWConfig()


def ev(dims, tiles, order="KCYXRS", par=(0, 1), shape=(16, 64), stride=1,
       dw=False, hw=HW, hard=False):
    return evaluate_mapping(
        jnp.asarray(dims), jnp.asarray(stride), jnp.asarray(dw),
        jnp.asarray(tiles), jnp.asarray(order_str_to_perm(order)),
        jnp.asarray(par), jnp.asarray(shape), hw, hard)


DIMS = st.tuples(st.integers(1, 256), st.integers(1, 64),
                 st.integers(1, 56), st.integers(1, 56),
                 st.integers(1, 7), st.integers(1, 7))


@given(DIMS, st.integers(0, 5 * 7 * 11))
@settings(max_examples=40, deadline=None)
def test_runtime_at_least_lower_bound(dims, seed):
    rng = np.random.default_rng(seed)
    tiles = [int(rng.integers(1, d + 1)) for d in dims]
    orders = ["KCYXRS", "YXKCRS", "CKSRXY"]
    r = ev(dims, tiles, order=orders[seed % 3])
    if bool(r.feasible):
        lb = lower_bound_cycles(np.asarray(dims), False, HW)
        assert float(r.runtime) >= lb * 0.999


@given(DIMS)
@settings(max_examples=30, deadline=None)
def test_util_in_unit_interval(dims):
    tiles = [min(d, t) for d, t in zip(dims, (64, 16, 3, 3, 3, 3))]
    r = ev(dims, tiles)
    assert 0.0 <= float(r.util) <= 1.0 + 1e-6


@given(DIMS, st.sampled_from(["KCYXRS", "YXKCRS", "KCRSYX", "CYXKRS"]))
@settings(max_examples=30, deadline=None)
def test_dram_traffic_at_least_compulsory(dims, order):
    """DRAM traffic >= one visit of each operand element (compulsory)."""
    tiles = [max(1, d // 2) for d in dims]
    r = ev(dims, tiles, order=order)
    if not bool(r.feasible):
        return
    k, c, y, x, rr, s = dims
    compulsory = c * y * x + k * c * rr * s + k * y * x
    # padded tiles may slightly exceed; compulsory is a floor
    assert float(r.dram_elems) >= 0.5 * compulsory


def test_bigger_buffer_never_hurts_feasibility():
    dims = (64, 32, 28, 28, 3, 3)
    tiles = (32, 16, 14, 14, 3, 3)
    small = ev(dims, tiles, hw=HWConfig(buffer_bytes=4 * 1024))
    big = ev(dims, tiles, hw=HWConfig(buffer_bytes=1024 * 1024))
    assert bool(big.feasible)
    if bool(small.feasible):
        assert float(big.runtime) == pytest.approx(float(small.runtime))


def test_hard_partition_stricter_than_soft():
    dims = (64, 64, 28, 28, 3, 3)
    hw = HWConfig(buffer_bytes=16 * 1024)
    for seed in range(10):
        rng = np.random.default_rng(seed)
        tiles = [int(rng.integers(1, d + 1)) for d in dims]
        soft = ev(dims, tiles, hw=hw, hard=False)
        hard = ev(dims, tiles, hw=hw, hard=True)
        if bool(hard.feasible):
            assert bool(soft.feasible), "hard-feasible must be soft-feasible"


def test_depthwise_kc_parallelism_starves():
    """Paper Layer-29: K=1 depthwise leaves K-C parallelism underutilized."""
    dims = (1, 480, 14, 14, 5, 5)
    tiles = (1, 480, 14, 14, 5, 5)
    kc = ev(dims, tiles, par=(0, 1), dw=True,
            hw=HWConfig(buffer_bytes=1024 * 1024))
    yx = ev(dims, tiles, par=(2, 3), dw=True,
            hw=HWConfig(buffer_bytes=1024 * 1024))
    assert float(yx.runtime) < float(kc.runtime)
    assert float(yx.util) > float(kc.util)


def test_order_changes_dram_traffic():
    """Weight-stationary vs output-stationary orders move DRAM traffic."""
    dims = (128, 64, 28, 28, 3, 3)
    tiles = (32, 16, 7, 7, 3, 3)
    rts = {o: float(ev(dims, tiles, order=o).dram_elems)
           for o in ("KCRSYX", "YXKCRS", "KCYXRS")}
    assert len(set(rts.values())) > 1, "orders should differentiate traffic"


def test_infeasible_marked_big():
    dims = (512, 512, 56, 56, 3, 3)
    tiles = (512, 512, 56, 56, 3, 3)  # way over 100KB
    r = ev(dims, tiles)
    assert not bool(r.feasible)
    assert float(r.runtime) > 1e29
