"""Plain reference of the analytical accelerator cost model that the
benchmark holds the program's answers to.

The semantics are those of the paper's Sec 3-5 model as this repository
states it (DRAM -> L2 buffer -> PE array; a mapping is tiles T, loop order
O, spatial pair P, array shape S and operand width R):

- a tile fits when its input, weight and output volumes (scaled by
  bits / native bits) fit the buffer together, or each a third of it on a
  hard-partitioned buffer; the spatial pair must be two distinct dims and
  the array at most ``num_pes``;
- runtime = max(compute, DRAM, L2 cycles) + (tiles - 1) x refill depth;
- a tensor is re-fetched from DRAM once per iteration of every loop at or
  outside its innermost dependent loop that iterates; L2 reads are divided
  by spatial multicast and by PE-level stationarity (capped at 64);
- energy = traffic x access energy (linear in width) + MACs x MAC energy
  (quadratic in width); an infeasible mapping costs 1e30.

Every function takes ``xp`` (numpy or jax.numpy) and a float ``dtype``:
the reference runs in numpy float64, the control in a lower precision.
Nothing here imports the program.
"""
from __future__ import annotations

import numpy as np

K, C, Y, X, R, S = range(6)
BIG = 1e30

# dependency of each tensor on (K, C, Y, X, R, S); a depthwise layer's
# weight and output follow C instead of K
DEP_IN = (0, 1, 1, 1, 1, 1)
DEP_W = (1, 1, 0, 0, 1, 1)
DEP_W_DW = (0, 1, 0, 0, 1, 1)
DEP_O = (1, 0, 1, 1, 0, 0)
DEP_O_DW = (0, 1, 1, 1, 0, 0)
PE_REG_CAP = 64.0


def _take(a, idx, xp):
    """Row-wise gather: ``out[n, j] = a[n, idx[n, j]]``."""
    return xp.take_along_axis(a, idx, axis=1)


def _fetch_mult(order, trips, dep, xp):
    """Product of trips of the loops at or outside the innermost loop that
    the tensor depends on and that iterates (at least 1)."""
    t_o = _take(trips, order, xp)
    rel = _take(dep, order, xp) & (t_o > 1)
    pos = xp.arange(6)[None, :]
    last = xp.max(xp.where(rel, pos, -1), axis=1, keepdims=True)
    return xp.maximum(xp.prod(xp.where(pos <= last, t_o, 1), axis=1), 1)


def _pe_reuse(order, tile, dep, xp):
    """Product of the tile extents of the loops inside the innermost
    dependent loop, clipped to [1, PE_REG_CAP]."""
    t_o = _take(tile, order, xp)
    rel = _take(dep, order, xp) & (t_o > 1)
    pos = xp.arange(6)[None, :]
    last = xp.max(xp.where(rel, pos, -1), axis=1, keepdims=True)
    return xp.clip(xp.prod(xp.where(pos > last, t_o, 1), axis=1), 1,
                   PE_REG_CAP)


def mapping_costs(m, hw, xp=np, dtype=np.float64):
    """Cost of N layer mappings.  ``m`` maps ``dims`` (N, 6), ``stride``
    (N,), ``depthwise`` (N,), ``tiles`` (N, 6), ``order`` (N, 6), ``par``
    (N, 2), ``shape`` (N, 2), ``bits`` (N,) and ``hard`` (N,) to arrays;
    ``hw`` is the configuration's hardware dict.  Returns (runtime, energy,
    feasible), each (N,)."""
    f = lambda a: xp.asarray(a).astype(dtype)          # noqa: E731
    one = f(1.0)
    dims = f(m["dims"])
    t = xp.clip(f(m["tiles"]), one, dims)
    order = xp.asarray(m["order"])
    par = xp.asarray(m["par"])
    dw = xp.asarray(m["depthwise"]).astype(bool)
    hard = xp.asarray(m["hard"]).astype(bool)
    stride = f(m["stride"])
    rows, cols = f(m["shape"][:, 0]), f(m["shape"][:, 1])
    bscale = f(m["bits"]) / f(8 * hw["bytes_per_elem"])
    mscale = bscale * bscale
    dep_i = xp.broadcast_to(xp.asarray(DEP_IN, bool), t.shape)
    dep_w = xp.where(dw[:, None], xp.asarray(DEP_W_DW, bool),
                     xp.asarray(DEP_W, bool))
    dep_o = xp.where(dw[:, None], xp.asarray(DEP_O_DW, bool),
                     xp.asarray(DEP_O, bool))

    vol_in = t[:, C] * ((t[:, Y] - 1) * stride + t[:, R]) \
        * ((t[:, X] - 1) * stride + t[:, S])
    vol_w = xp.where(dw, one, t[:, K]) * t[:, C] * t[:, R] * t[:, S]
    vol_out = xp.where(dw, t[:, C], t[:, K]) * t[:, Y] * t[:, X]
    buf = f(hw["buffer_bytes"] // hw["bytes_per_elem"])
    fits_hard = ((vol_in * bscale <= buf / 3) & (vol_w * bscale <= buf / 3)
                 & (vol_out * bscale <= buf / 3))
    fits_soft = (vol_in + vol_w + vol_out) * bscale <= buf
    feasible = (xp.where(hard, fits_hard, fits_soft)
                & (par[:, 0] != par[:, 1]) & (rows >= 1) & (cols >= 1)
                & (rows * cols <= hw["num_pes"]))

    trips = xp.ceil(dims / t)
    n_tiles = xp.prod(trips, axis=1)
    tile_macs = xp.prod(t, axis=1) / xp.where(dw, t[:, K], one)
    macs = n_tiles * tile_macs
    tp1 = _take(t, par[:, :1], xp)[:, 0]
    tp2 = _take(t, par[:, 1:], xp)[:, 0]
    folds = xp.ceil(tp1 / rows) * xp.ceil(tp2 / cols)
    compute = n_tiles * (folds * tile_macs / (tp1 * tp2)) * bscale

    out_mult = _fetch_mult(order, trips, dep_o, xp)
    distinct_out = xp.prod(xp.where(dep_o, trips, one), axis=1)
    dram = (vol_in * _fetch_mult(order, trips, dep_i, xp)
            + vol_w * _fetch_mult(order, trips, dep_w, xp)
            + vol_out * (distinct_out
                         + 2 * xp.maximum(out_mult - distinct_out, 0)))
    a1, a2 = xp.minimum(tp1, rows), xp.minimum(tp2, cols)

    def multicast(dep):
        d1 = _take(dep, par[:, :1], xp)[:, 0]
        d2 = _take(dep, par[:, 1:], xp)[:, 0]
        return xp.where(d1, one, a1) * xp.where(d2, one, a2)

    l2 = sum(macs / (multicast(d) * _pe_reuse(order, t, d, xp))
             for d in (dep_i, dep_w, dep_o))
    runtime = xp.maximum(xp.maximum(compute, dram * bscale / hw["dram_bw"]),
                         l2 * bscale / hw["l2_bw"]) \
        + (n_tiles - 1) * (a1 + a2)
    energy = (dram * hw["e_dram"] * bscale + l2 * hw["e_l2"] * bscale
              + 3 * macs * hw["e_l1"] * bscale + macs * hw["e_mac"] * mscale)
    big = f(BIG)
    return (xp.where(feasible, runtime, big), xp.where(feasible, energy, big),
            feasible)

