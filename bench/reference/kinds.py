"""Plain reference of the grouped and ragged layer kinds: the mapping cost
and the buffer-fit predicate of docs/mapper.md "Layer kinds", written from
its equations.  Plain and depthwise layers go to ``costmodel.py``.

A layer's nest is ``(K, C, Y, X, R, S)``; a mapping's tile is clipped to
it.  A grouped layer holds ``G = X`` GEMMs, each with its own weights: the
weight depends on K, C, X, R and S, the input on C, Y, X, R and S, the
output on K, Y and X.  A ragged layer is grouped with its own rows ``n_g``
per group and one mapping for all: its cost is the sum over its groups of
the GEMM ``(K, C, n_g, 1, R, S)``, each at the tile clipped to it, and it is
feasible where its largest group is.  An infeasible layer costs 1e30.

Every function takes ``xp`` (numpy or jax.numpy) and a float ``dtype``: the
reference runs in numpy float64, the control in a lower precision.  Nothing
here imports the program, nor anything else.
"""
from __future__ import annotations

import numpy as np

K, C, Y, X, R, S = range(6)
BIG = 1e30
PE_REG_CAP = 64.0
DEP_IN = (0, 1, 1, 1, 1, 1)
DEP_W = (1, 1, 0, 1, 1, 1)      # a group's weights are its own
DEP_OUT = (1, 0, 1, 1, 0, 0)


def _take(a, idx, xp):
    return xp.take_along_axis(a, idx, axis=1)


def _prod_through_last(order, ext, dep, xp, outside: bool):
    """Product of ``ext`` over the loops at or outside (``outside``) or
    strictly inside the innermost loop in ``dep`` whose extent is above 1."""
    e_o = _take(ext, order, xp)
    rel = _take(dep, order, xp) & (e_o > 1)
    pos = xp.arange(6)[None, :]
    last = xp.max(xp.where(rel, pos, -1), axis=1, keepdims=True)
    keep = pos <= last if outside else pos > last
    return xp.prod(xp.where(keep, e_o, 1), axis=1)


def grouped_costs(m, hw, xp=np, dtype=np.float64):
    """Runtime, energy and feasibility of N grouped-GEMM mappings; ``m``
    holds ``dims``, ``stride``, ``tiles``, ``order``, ``par``, ``shape``,
    ``bits`` and ``hard`` arrays, as ``costmodel.mapping_costs`` takes
    them."""
    f = lambda a: xp.asarray(a).astype(dtype)          # noqa: E731
    one = f(1.0)
    dims = f(m["dims"])
    t = xp.clip(f(m["tiles"]), one, dims)
    order = xp.asarray(m["order"])
    par = xp.asarray(m["par"])
    hard = xp.asarray(m["hard"]).astype(bool)
    stride = f(m["stride"])
    rows, cols = f(m["shape"][:, 0]), f(m["shape"][:, 1])
    b = f(m["bits"]) / f(8 * hw["bytes_per_elem"])
    deps = [xp.broadcast_to(xp.asarray(d, bool), t.shape)
            for d in (DEP_IN, DEP_W, DEP_OUT)]

    vol_in = t[:, C] * ((t[:, Y] - 1) * stride + t[:, R]) \
        * ((t[:, X] - 1) * stride + t[:, S])
    vol_w = t[:, K] * t[:, C] * t[:, X] * t[:, R] * t[:, S]
    vol_out = t[:, K] * t[:, Y] * t[:, X]
    buf = f(hw["buffer_bytes"] // hw["bytes_per_elem"])
    fits = xp.where(hard,
                    (vol_in * b <= buf / 3) & (vol_w * b <= buf / 3)
                    & (vol_out * b <= buf / 3),
                    (vol_in + vol_w + vol_out) * b <= buf)
    feasible = (fits & (par[:, 0] != par[:, 1]) & (rows >= 1) & (cols >= 1)
                & (rows * cols <= hw["num_pes"]))

    trips = xp.ceil(dims / t)
    n_tiles = xp.prod(trips, axis=1)
    tile_macs = xp.prod(t, axis=1)
    macs = n_tiles * tile_macs
    tp1 = _take(t, par[:, :1], xp)[:, 0]
    tp2 = _take(t, par[:, 1:], xp)[:, 0]
    compute = n_tiles * (xp.ceil(tp1 / rows) * xp.ceil(tp2 / cols)
                         * tile_macs / (tp1 * tp2)) * b

    dep_o = deps[2]
    m_out = _prod_through_last(order, trips, dep_o, xp, True)
    distinct_out = xp.prod(xp.where(dep_o, trips, one), axis=1)
    dram = (vol_in * xp.maximum(
                _prod_through_last(order, trips, deps[0], xp, True), 1)
            + vol_w * xp.maximum(
                _prod_through_last(order, trips, deps[1], xp, True), 1)
            + vol_out * (distinct_out
                         + 2 * xp.maximum(xp.maximum(m_out, 1)
                                          - distinct_out, 0)))
    a1, a2 = xp.minimum(tp1, rows), xp.minimum(tp2, cols)

    def l2_reads(dep):
        d1 = _take(dep, par[:, :1], xp)[:, 0]
        d2 = _take(dep, par[:, 1:], xp)[:, 0]
        mcast = xp.where(d1, one, a1) * xp.where(d2, one, a2)
        reuse = xp.clip(_prod_through_last(order, t, dep, xp, False), 1,
                        PE_REG_CAP)
        return macs / (mcast * reuse)

    l2 = sum(l2_reads(d) for d in deps)
    runtime = xp.maximum(xp.maximum(compute, dram * b / hw["dram_bw"]),
                         l2 * b / hw["l2_bw"]) + (n_tiles - 1) * (a1 + a2)
    energy = (dram * hw["e_dram"] * b + l2 * hw["e_l2"] * b
              + 3 * macs * hw["e_l1"] * b + macs * hw["e_mac"] * b * b)
    big = f(BIG)
    return (xp.where(feasible, runtime, big), xp.where(feasible, energy, big),
            feasible)


def expand_groups(m, group_rows):
    """The ragged rows of ``m`` as one plain row per group with rows:
    ``(group mapping table, owner row of each group row)``; ``group_rows``
    lists each row's per-group rows."""
    owner, dims = [], []
    for i, rows in enumerate(group_rows):
        k, c, _, _, r, s = (int(v) for v in m["dims"][i])
        for n in rows:
            if n > 0:
                owner.append(i)
                dims.append((k, c, int(n), 1, r, s))
    owner = np.asarray(owner, np.int64)
    g = {key: np.asarray(v)[owner] for key, v in m.items()}
    g["dims"] = np.asarray(dims, np.int64).reshape(-1, 6)
    return g, owner


def ragged_costs(m, group_rows, hw, xp=np, dtype=np.float64):
    """Runtime, energy and feasibility of N ragged-GEMM mappings: the sums
    over each row's groups, under the row's one mapping, of the grouped
    cost (a group of one GEMM is a plain GEMM); infeasible unless every
    group fits, which is where the largest one does."""
    g, owner = expand_groups(m, group_rows)
    rt, en, ok = grouped_costs(g, hw, xp, dtype)
    n = len(group_rows)
    f = lambda a: xp.asarray(a).astype(dtype)          # noqa: E731
    seg = xp.asarray(owner)
    if xp is np:
        rt_sum, en_sum, bad = (np.zeros(n, dtype), np.zeros(n, dtype),
                               np.zeros(n, np.int64))
        np.add.at(rt_sum, owner, rt)
        np.add.at(en_sum, owner, en)
        np.add.at(bad, owner, (~ok).astype(np.int64))
    else:
        rt_sum = xp.zeros(n, dtype).at[seg].add(rt)
        en_sum = xp.zeros(n, dtype).at[seg].add(en)
        bad = xp.zeros(n, xp.int32).at[seg].add((~ok).astype(xp.int32))
    feasible = bad == 0
    big = f(BIG)
    return (xp.where(feasible, rt_sum, big), xp.where(feasible, en_sum, big),
            feasible)


def fit_shares(draws, stride, grouped: bool, buf, xp=np, dtype=np.float64):
    """Shares of a (non-depthwise) layer's tile samples that fit the shared
    buffer (soft) and a buffer hard-partitioned into thirds (hard), on the
    same samples; a grouped layer's weight tile spans t_X too."""
    t = xp.asarray(draws).astype(dtype)
    s = xp.asarray(stride).astype(dtype)
    k, c, y, x, r, q = (t[i] for i in range(6))
    vin = c * ((y - 1) * s + r) * ((x - 1) * s + q)
    vw = k * c * r * q * (x if grouped else 1)
    vout = k * y * x
    b = xp.asarray(buf).astype(dtype)
    soft = (vin + vw + vout) <= b
    hard = (vin <= b / 3) & (vw <= b / 3) & (vout <= b / 3)
    return xp.mean(soft.astype(dtype)), xp.mean(hard.astype(dtype))
