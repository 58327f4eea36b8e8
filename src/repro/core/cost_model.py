"""Analytical accelerator cost model (MAESTRO/Timeloop-style), fully
vectorizable with ``jax.vmap`` so a whole GA population evaluates in one jit.

Hierarchy modelled (paper Fig 1/Fig 4): DRAM -> L2 global buffer -> PE array.
A *mapping* is (T, O, P, S):

  T : L2 tile sizes (t_K, t_C, t_Y, t_X, t_R, t_S)
  O : permutation of the 6 loops (outermost first) for the DRAM->L2 loops,
      reused intra-tile for PE-level stationarity
  P : ordered pair of dims spatially mapped to (rows, cols)
  S : logical array shape (rows, cols), rows*cols <= num_PEs

Loop-nest reuse analysis: a tensor with dependency set D must be re-fetched
once per iteration of every loop at or outside its innermost dependent loop;
loops strictly inside give free temporal reuse (the "stationary" window).

Runtime = max(compute, DRAM, L2) cycles (double-buffered) + tile-switch
stalls (systolic refill, paper Fig 3a).  Energy = per-access energies times
traffic at each level plus MAC energy.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .precision import element_scale, mac_scale, native_bits
from .spec import HWConfig
from .workloads import C, K, NUM_DIMS, R, S, X, Y

BIG = jnp.float32(1e30)

# Dependency masks over (K, C, Y, X, R, S); depthwise swaps K-dependence for
# C, a grouped layer's weight also depends on X (docs/mapper.md "Layer kinds").
_DEP_IN = np.array([0, 1, 1, 1, 1, 1], np.bool_)       # input
_DEP_W = np.array([1, 1, 0, 0, 1, 1], np.bool_)        # weight
_DEP_O = np.array([1, 0, 1, 1, 0, 0], np.bool_)        # output
_DEP_W_DW = np.array([0, 1, 0, 0, 1, 1], np.bool_)     # depthwise weight
_DEP_O_DW = np.array([0, 1, 1, 1, 0, 0], np.bool_)     # depthwise output
_DEP_W_G = np.array([1, 1, 0, 1, 1, 1], np.bool_)      # grouped weight


class CostResult(NamedTuple):
    runtime: jnp.ndarray       # cycles
    energy: jnp.ndarray        # relative pJ (MAC = 1)
    feasible: jnp.ndarray      # bool
    util: jnp.ndarray          # average PE utilization in [0, 1]
    dram_elems: jnp.ndarray    # total DRAM traffic (elements)
    l2_elems: jnp.ndarray      # total L2 traffic (elements)
    edp: jnp.ndarray           # energy-delay product


def _ceil_div(a, b):
    return (a + b - 1) // b


def _pick(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``x[idx]`` for a per-dimension ``(6,)`` vector ``x`` at a traced index
    array ``idx`` of any shape, as a compare against the dimension axis and a
    masked reduction: no gather, which the TPU lowers per index once ``vmap``
    batches it (docs/mapper.md "Batched engine dataflow").  Exact: one term
    of the sum is ``x[idx]`` and the other five are exact zeros (only a
    -0.0 would read +0.0; trip counts and tile sizes are at least 1)."""
    hit = idx[..., None] == jnp.arange(NUM_DIMS)
    if x.dtype == jnp.bool_:
        return jnp.any(hit & x, axis=-1)
    return jnp.sum(jnp.where(hit, x, 0), axis=-1)


def _reuse_multiplier(order: jnp.ndarray, trips: jnp.ndarray,
                      dep: jnp.ndarray) -> jnp.ndarray:
    """prod of trip counts of loops at-or-outside the innermost dependent loop.

    order: (6,) dim index per position (0 = outermost)
    trips: (6,) per-dim trip count
    dep:   (6,) per-dim bool dependency
    """
    dep_in_order = _pick(dep, order)                # (6,) by position
    pos = jnp.arange(NUM_DIMS)
    # innermost position whose dim is relevant AND actually iterates (>1 trips)
    trips_in_order = _pick(trips, order)
    relevant = dep_in_order & (trips_in_order > 1)
    p_last = jnp.max(jnp.where(relevant, pos, -1))
    mult = jnp.prod(jnp.where(pos <= p_last, trips_in_order, 1))
    return jnp.maximum(mult, 1)


def _stationary_reuse(order: jnp.ndarray, tile: jnp.ndarray,
                      dep: jnp.ndarray, cap: float = 64.0) -> jnp.ndarray:
    """Temporal reuse of a tensor inside the PE (L1) = product of tile sizes of
    loops strictly inside its innermost dependent loop, capped by register
    capacity.  This is what the O axis buys at the L2-access level."""
    dep_in_order = _pick(dep, order)
    pos = jnp.arange(NUM_DIMS)
    tile_in_order = _pick(tile, order)
    relevant = dep_in_order & (tile_in_order > 1)
    p_last = jnp.max(jnp.where(relevant, pos, -1))
    reuse = jnp.prod(jnp.where(pos > p_last, tile_in_order, 1))
    return jnp.clip(reuse, 1.0, cap)


def evaluate_mapping_impl(dims: jnp.ndarray, stride: jnp.ndarray,
                          depthwise: jnp.ndarray,
                          tiles: jnp.ndarray, order: jnp.ndarray,
                          par: jnp.ndarray, shape_rc: jnp.ndarray,
                          hw: HWConfig, hard_partition,
                          repr_bits=None, grouped=None) -> CostResult:
    """Cost one mapping of one layer.  All args are arrays => vmap-friendly.

    dims: (6,) int   layer (K, C, Y, X, R, S)
    stride: () int   conv stride
    depthwise: () bool
    tiles: (6,) int  L2 tile sizes (clipped to dims)
    order: (6,) int  permutation, outermost first
    par:   (2,) int  dims mapped to (rows, cols)
    shape_rc: (2,) int  (rows, cols)
    hard_partition: () bool — may be a *traced* array, so one compiled
        program can evaluate rows of different flexibility specs (the batched
        engine batches a whole model, optionally several specs, per dispatch).
    repr_bits: () int operand bit-width (R axis), or None for the native
        width.  Buffer occupancy, DRAM/L2 traffic/bandwidth, access energies
        and compute throughput all scale linearly with bits/native (subword
        SIMD below native, bit-serial above); MAC energy quadratically.  At
        the native width every scale is exactly 1.0 — an IEEE-exact identity,
        so pinned-R results are bit-identical to the pre-R model.
    grouped: () bool, or None when no row of the batch is grouped (the
        program then holds no grouped term).  A grouped layer's weight
        depends on X: its tile volume gains t_X and its reuse the X loop.
    """
    if repr_bits is None:
        bscale = jnp.float32(1.0)
        mscale = jnp.float32(1.0)
    else:
        nb = jnp.float32(native_bits(hw))
        bscale = element_scale(repr_bits.astype(jnp.float32), nb)
        mscale = mac_scale(repr_bits.astype(jnp.float32), nb)
    dims = dims.astype(jnp.float32)
    t = jnp.clip(tiles.astype(jnp.float32), 1.0, dims)
    rows = shape_rc[0].astype(jnp.float32)
    cols = shape_rc[1].astype(jnp.float32)
    stride = stride.astype(jnp.float32)

    dep_w = jnp.where(depthwise, jnp.asarray(_DEP_W_DW), jnp.asarray(_DEP_W))
    if grouped is not None:
        dep_w = jnp.where(grouped, jnp.asarray(_DEP_W_G), dep_w)
    dep_o = jnp.where(depthwise, jnp.asarray(_DEP_O_DW), jnp.asarray(_DEP_O))
    dep_i = jnp.asarray(_DEP_IN)

    # ---- tile volumes (elements) ------------------------------------------
    in_y = (t[Y] - 1.0) * stride + t[R]
    in_x = (t[X] - 1.0) * stride + t[S]
    vol_in = t[C] * in_y * in_x
    vol_w = jnp.where(depthwise, 1.0, t[K]) * t[C] * t[R] * t[S]
    if grouped is not None:
        # a select, not a multiply by 1.0: plain rows keep the plain value
        vol_w = jnp.where(grouped, vol_w * t[X], vol_w)
    vol_out = jnp.where(depthwise, t[C], t[K]) * t[Y] * t[X]

    buf = jnp.float32(hw.buffer_elems)
    cap = buf / 3.0
    fits_part = (vol_in * bscale <= cap) & (vol_w * bscale <= cap) \
        & (vol_out * bscale <= cap)
    fits_shared = (vol_in + vol_w + vol_out) * bscale <= buf
    fits = jnp.where(jnp.asarray(hard_partition), fits_part, fits_shared)

    # parallel dims must be distinct and the array must exist
    par_ok = (par[0] != par[1]) & (rows >= 1) & (cols >= 1) \
        & (rows * cols <= hw.num_pes)
    feasible = fits & par_ok

    # ---- trip counts & compute --------------------------------------------
    trips = _ceil_div(dims, t)                      # (6,) DRAM-level loops
    num_tiles = jnp.prod(trips)
    tile_macs = jnp.prod(t) / jnp.where(depthwise, t[K], 1.0)
    total_macs = num_tiles * tile_macs              # padded (folded) MACs

    tp1 = _pick(t, par[0])
    tp2 = _pick(t, par[1])
    folds = _ceil_div(tp1, rows) * _ceil_div(tp2, cols)
    serial_iters = folds * tile_macs / (tp1 * tp2)  # cycles per tile
    # throughput scales with operand width (subword SIMD / bit-serial)
    compute_cycles = num_tiles * serial_iters * bscale
    active = jnp.minimum(tp1, rows) * jnp.minimum(tp2, cols)
    # average utilization incl. folding remainder
    ideal_cycles = num_tiles * tile_macs / (rows * cols) * bscale
    util = ideal_cycles / jnp.maximum(compute_cycles, 1.0)

    # ---- DRAM traffic via loop-nest reuse ---------------------------------
    dram_in = vol_in * _reuse_multiplier(order, trips, dep_i)
    dram_w = vol_w * _reuse_multiplier(order, trips, dep_w)
    out_mult = _reuse_multiplier(order, trips, dep_o)
    distinct_out = jnp.prod(jnp.where(dep_o, trips, 1))
    psum_revisits = jnp.maximum(out_mult - distinct_out, 0.0)
    dram_out = vol_out * (distinct_out + 2.0 * psum_revisits)
    dram_elems = dram_in + dram_w + dram_out
    dram_cycles = dram_elems * bscale / hw.dram_bw

    # ---- L2 traffic: spatial multicast + PE-level stationarity ------------
    def mcast(dep):
        f1 = jnp.where(_pick(dep, par[0]), 1.0, jnp.minimum(tp1, rows))
        f2 = jnp.where(_pick(dep, par[1]), 1.0, jnp.minimum(tp2, cols))
        return f1 * f2

    l2_in = total_macs / (mcast(dep_i) * _stationary_reuse(order, t, dep_i))
    l2_w = total_macs / (mcast(dep_w) * _stationary_reuse(order, t, dep_w))
    l2_out = total_macs / (mcast(dep_o) * _stationary_reuse(order, t, dep_o))
    l2_elems = l2_in + l2_w + l2_out
    l2_cycles = l2_elems * bscale / hw.l2_bw

    # ---- stalls: stationary-tile switch == systolic refill (Fig 3a) -------
    # refill depth follows the *active* extent of the array (idle rows/cols
    # are clock-gated and do not lengthen the pipeline)
    stalls = (num_tiles - 1.0) * (jnp.minimum(tp1, rows)
                                  + jnp.minimum(tp2, cols))

    runtime = jnp.maximum(jnp.maximum(compute_cycles, dram_cycles),
                          l2_cycles) + stalls
    runtime = jnp.where(feasible, runtime, BIG)

    # ---- energy ------------------------------------------------------------
    # access energies scale linearly with width, MAC energy quadratically
    l1_accesses = 3.0 * total_macs
    energy = (dram_elems * hw.e_dram * bscale + l2_elems * hw.e_l2 * bscale
              + l1_accesses * hw.e_l1 * bscale
              + total_macs * hw.e_mac * mscale)
    energy = jnp.where(feasible, energy, BIG)

    return CostResult(
        runtime=runtime, energy=energy, feasible=feasible,
        util=jnp.where(feasible, util, 0.0),
        dram_elems=dram_elems, l2_elems=l2_elems,
        edp=jnp.where(feasible, runtime * energy, BIG),
    )


def evaluate_groups_impl(group_dims: jnp.ndarray, group_live: jnp.ndarray,
                         stride: jnp.ndarray, depthwise: jnp.ndarray,
                         tiles: jnp.ndarray, order: jnp.ndarray,
                         par: jnp.ndarray, shape_rc: jnp.ndarray,
                         hw: HWConfig, hard_partition, repr_bits=None,
                         grouped=None) -> CostResult:
    """Cost one mapping of a layer as the sum over its groups (the ragged
    kind, docs/mapper.md "Layer kinds"): ``group_dims`` (G, 6) nests and
    their ``group_live`` (G,) mask; every live group is costed under the
    same mapping, its tiles clipped to its own dims.  Runtime, energy and
    traffic add up; the layer is feasible where its largest group is (the
    tile volumes grow with the rows, so that is where every group is);
    utilization is the groups' average weighted by their rows.  A layer of
    one live group costs what ``evaluate_mapping_impl`` gives it, up to
    how XLA fuses the larger program (float32 rounding)."""

    def one(d_):
        return evaluate_mapping_impl(d_, stride, depthwise, tiles, order,
                                     par, shape_rc, hw, hard_partition,
                                     repr_bits, grouped)

    with jax.named_scope("evaluate_ragged"):
        res = jax.vmap(one)(group_dims)

        def total(f):
            return jnp.sum(jnp.where(group_live, f, 0.0))

        feasible = jnp.all(res.feasible | ~group_live)
        rows = jnp.where(group_live, group_dims[:, Y], 0).astype(jnp.float32)
        runtime = jnp.where(feasible, total(res.runtime), BIG)
        energy = jnp.where(feasible, total(res.energy), BIG)
        util = jnp.sum(res.util * (rows / jnp.maximum(jnp.sum(rows), 1.0)))
        return CostResult(
            runtime=runtime, energy=energy, feasible=feasible,
            util=jnp.where(feasible, util, 0.0),
            dram_elems=total(res.dram_elems), l2_elems=total(res.l2_elems),
            edp=jnp.where(feasible, runtime * energy, BIG))


def evaluate_kinds_impl(dims, stride, depthwise, tiles, order, par,
                        shape_rc, hw: HWConfig, hard_partition,
                        repr_bits=None, grouped=None,
                        groups=None) -> CostResult:
    """One mapping of one layer of any kind: ``groups`` is ``None`` (the
    plain, depthwise and grouped kinds) or the layer's ``(group_dims,
    group_live)`` pair, the ragged program variant."""
    if groups is None:
        return evaluate_mapping_impl(dims, stride, depthwise, tiles, order,
                                     par, shape_rc, hw, hard_partition,
                                     repr_bits, grouped)
    return evaluate_groups_impl(groups[0], groups[1], stride, depthwise,
                                tiles, order, par, shape_rc, hw,
                                hard_partition, repr_bits, grouped)


@partial(jax.jit, static_argnames=("hw", "hard_partition"))
def evaluate_mapping(dims: jnp.ndarray, stride: jnp.ndarray,
                     depthwise: jnp.ndarray,
                     tiles: jnp.ndarray, order: jnp.ndarray,
                     par: jnp.ndarray, shape_rc: jnp.ndarray,
                     hw: HWConfig, hard_partition: bool = False,
                     repr_bits=None, grouped=None,
                     groups=None) -> CostResult:
    """Jitted single-mapping entry point (static hard_partition);
    ``grouped`` and ``groups`` as :func:`evaluate_kinds_impl` takes them."""
    return evaluate_kinds_impl(dims, stride, depthwise, tiles, order, par,
                               shape_rc, hw, hard_partition, repr_bits,
                               grouped, groups)


@partial(jax.jit, static_argnames=("hw", "hard_partition"))
def evaluate_population(dims: jnp.ndarray, stride: jnp.ndarray,
                        depthwise: jnp.ndarray,
                        tiles: jnp.ndarray, order: jnp.ndarray,
                        par: jnp.ndarray, shape_rc: jnp.ndarray,
                        hw: HWConfig, hard_partition: bool = False,
                        reprs=None, grouped=None,
                        groups=None) -> CostResult:
    """vmap of evaluate_mapping over a (P, ...) population of mappings;
    ``grouped`` and ``groups`` as :func:`evaluate_kinds_impl` takes them."""

    def one(t_, o_, p_, s_, r_):
        return evaluate_kinds_impl(dims, stride, depthwise, t_, o_, p_, s_,
                                   hw, hard_partition, r_, grouped, groups)

    return jax.vmap(one)(tiles, order, par, shape_rc, reprs)


@partial(jax.jit, static_argnames=("hw",))
def evaluate_rows(dims: jnp.ndarray, stride: jnp.ndarray,
                  depthwise: jnp.ndarray,
                  tiles: jnp.ndarray, order: jnp.ndarray,
                  par: jnp.ndarray, shape_rc: jnp.ndarray,
                  hard_partition: jnp.ndarray, hw: HWConfig,
                  reprs=None, grouped=None, groups=None) -> CostResult:
    """Batch-axis plumbing for the MSE engine: one mapping per *row*, where a
    row is a (layer, spec) pair — every array carries a leading (L,) axis,
    including the (traced) per-row hard-partition flag (and, when given, the
    per-row operand bit-width, grouped flag and ``(group_dims, group_live)``
    table of the ragged variant)."""

    def one(d_, s_, w_, t_, o_, p_, sh_, hp_, r_, g_, gr_):
        return evaluate_kinds_impl(d_, s_, w_, t_, o_, p_, sh_, hw, hp_, r_,
                                   g_, gr_)

    with jax.named_scope("evaluate_rows"):
        return jax.vmap(one)(dims, stride, depthwise, tiles, order, par,
                             shape_rc, hard_partition, reprs, grouped,
                             groups)


def lower_bound_cycles(dims: np.ndarray, depthwise: bool,
                       hw: HWConfig) -> float:
    """Roofline lower bound: max(compute at full PE util, min DRAM traffic)."""
    k, c, y, x, r, s = [float(v) for v in dims]
    macs = (c if depthwise else k * c) * y * x * r * s
    in_elems = c * y * x          # >= one read of each input element
    w_elems = (1 if depthwise else k) * c * r * s
    o_elems = (c if depthwise else k) * y * x
    return max(macs / hw.num_pes, (in_elems + w_elems + o_elems) / hw.dram_bw)
