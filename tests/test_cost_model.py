"""Cost-model unit + property tests (hypothesis): physical invariants."""
import hashlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import HWConfig, get_model, lower_bound_cycles
from repro.core.cost_model import _pick, evaluate_mapping, evaluate_population
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


ORDERS = np.array(list(itertools.permutations(range(6))), np.int32)
PAIRS = np.array(list(itertools.product(range(6), repeat=2)), np.int32)
PICK_VECTORS = {
    "bool": np.array([1, 0, 0, 1, 1, 0], np.bool_),
    "int32": np.array([7, 2**24 + 1, -3, 0, 2**31 - 1, 1], np.int32),
    "float32": np.array([1e30, 2.0**24 + 2, 0.1, 3e38, 3.5, 1.0],
                        np.float32),
}


@pytest.mark.parametrize("batching", ["array", "vmap"])
@pytest.mark.parametrize("index", ["orders", "pairs"])
@pytest.mark.parametrize("dtype", sorted(PICK_VECTORS))
def test_pick_is_exact_indexing(dtype, index, batching):
    """``_pick`` gives numpy's ``x[idx]`` bit for bit, for every one of the
    720 loop orders and every (par[0], par[1]) pair, as an index array and
    under ``vmap`` (how the cost model calls it per mapping)."""
    x = PICK_VECTORS[dtype]
    idx = ORDERS if index == "orders" else PAIRS
    if batching == "array":
        got = jax.jit(_pick)(jnp.asarray(x), jnp.asarray(idx))
    else:
        got = jax.jit(jax.vmap(_pick, in_axes=(None, 0)))(
            jnp.asarray(x), jnp.asarray(idx))
    got = np.asarray(got)
    want = x[idx]
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


# runtime and energy of resnet50's conv2.0.conv2 at six of the 720 orders,
# and a digest of all 720, as the cost model gave them when it indexed its
# per-dimension vectors by gathers
ORDER_COSTS = {
    "KCYXRS": (608226.0, 2383164160.0),
    "KCYRSX": (862946.0, 3881186048.0),
    "KCXRSY": (872162.0, 3907138304.0),
    "KCRSYX": (856034.0, 3852763904.0),
    "KYXCRS": (608226.0, 1618883456.0),
    "CYXRSK": (798434.0, 3676572416.0),
}
ORDER_COSTS_SHA256 = \
    "7bd6281e29a46a7e9e839c7c966870492fcd3562d2dccd603d37e69d6478ce23"


def test_every_order_costs_what_it_did():
    """``evaluate_population`` over the 720 loop orders of one layer, with
    its other genes fixed, returns the same bits as before the cost model
    read its loop orders by compare and select."""
    layer = next(l for l in get_model("resnet50")
                 if l.dims == (64, 64, 56, 56, 3, 3))
    n = len(ORDERS)
    r = evaluate_population(
        np.asarray(layer.dims, np.int32), np.int32(layer.stride),
        np.bool_(layer.depthwise),
        np.tile(np.array((16, 8, 14, 7, 3, 1), np.int32), (n, 1)), ORDERS,
        np.tile(np.array([[0, 2]], np.int32), (n, 1)),
        np.tile(np.array([[16, 32]], np.int32), (n, 1)), HWConfig(), False)
    runtime, energy = np.asarray(r.runtime), np.asarray(r.energy)
    names = ["".join("KCYXRS"[d] for d in o) for o in ORDERS]
    for name, cost in ORDER_COSTS.items():
        i = names.index(name)
        assert (float(runtime[i]), float(energy[i])) == cost, name
    digest = hashlib.sha256(runtime.tobytes() + energy.tobytes()).hexdigest()
    assert digest == ORDER_COSTS_SHA256
