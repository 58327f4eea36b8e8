"""Plain reference of the flexion columns (paper Table 1): H-F = |A_X| /
|C_X| and W-F = |A_X^w| / |W_X^w|, as products over the T/O/P/S/R axes.

O, P, S and R are counted exactly.  The T axis is a Monte-Carlo share of
tile samples that fit the buffer: against the workload-agnostic domain
[1, 256]^4 x [1, 11]^2 for H-F, against the layer's own dims for W-F.  The
H-F reference accelerator opens T/O/P/S fully and opens R only when the
accelerator does.  Nothing here imports the program.
"""
from __future__ import annotations

import numpy as np

AGNOSTIC_DIMS = (256, 256, 256, 256, 11, 11)
# operand-width menus: pinned, the quantised-inference menu, bit-serial
R_CHOICES = {"inflex": 1, "part": 3, "full": 5}


def tile_draws(dims, seed: int, n: int) -> np.ndarray:
    """(6, n) tile samples uniform over prod [1, d]: numpy's PCG64 stream
    seeded with ``seed``, one ``integers`` draw per dim in K..S order.  The
    samples are the estimator's data, shared with the program by
    definition."""
    rng = np.random.default_rng(seed)
    return np.stack([rng.integers(1, int(d) + 1, n) for d in dims])


def fit_shares(draws, stride, depthwise, buf, xp=np, dtype=np.float64):
    """Shares of the samples whose tile fits the shared buffer (soft) and a
    buffer hard-partitioned into thirds (hard), on the same samples."""
    t = xp.asarray(draws).astype(dtype)
    s = xp.asarray(stride).astype(dtype)
    k, c, y, x, r, q = (t[i] for i in range(6))
    vin = c * ((y - 1) * s + r) * ((x - 1) * s + q)
    vw = (c if depthwise else k * c) * r * q
    vout = (c if depthwise else k) * y * x
    b = xp.asarray(buf).astype(dtype)
    soft = (vin + vw + vout) <= b
    hard = (vin <= b / 3) & (vw <= b / 3) & (vout <= b / 3)
    return xp.mean(soft.astype(dtype)), xp.mean(hard.astype(dtype))


def _choices(level: str, num_pes: int):
    """Choices of the O, P and S axes at a flexibility level: one each when
    inflexible; the paper's menus when partly flexible (3 stationarities,
    {K-C, Y-X}, shapes built from 16 x 16 blocks); every order, ordered
    pair and (rows, num_pes // rows) shape when fully flexible."""
    blocks = num_pes // 256
    part_shapes = sum(1 for a in range(1, blocks + 1)
                      for c in range(1, blocks + 1) if a * c <= blocks)
    return {"inflex": (1, 1, 1), "part": (3, 2, part_shapes),
            "full": (720, 30, num_pes)}[level]


def columns(rows, layers, hw, n: int, xp=np, dtype=np.float64):
    """H-F and W-F of each accelerator row.

    ``rows``: ``{row name: {axis: level}}`` over axes T, O, P, S, R with
    levels ``inflex`` / ``part`` / ``full``.  ``layers``: the future suite
    ``[(name, dims, stride, depthwise), ...]``; layer ``i`` draws its tile
    samples with seed ``i``, the agnostic domain with seed 0.  Returns
    ``({row: hf}, {row: wf})``."""
    f = lambda v: xp.asarray(v).astype(dtype)          # noqa: E731
    buf = hw["buffer_bytes"] // hw["bytes_per_elem"]
    pes = hw["num_pes"]
    ref_soft, ref_hard = fit_shares(tile_draws(AGNOSTIC_DIMS, 0, n), 1,
                                    False, buf, xp, dtype)
    agn_volume = f(float(np.prod(np.asarray(AGNOSTIC_DIMS, np.float64))))
    t_open = any(lv["T"] != "inflex" for lv in rows.values())
    shares = [fit_shares(tile_draws(d, i, n), st, dw, buf, xp, dtype)
              if t_open else None
              for i, (_, d, st, dw) in enumerate(layers)]
    full_o, full_p, full_s = _choices("full", pes)
    hf, wf = {}, {}
    for name, lv in rows.items():
        n_o = _choices(lv["O"], pes)[0]
        n_p = _choices(lv["P"], pes)[1]
        n_s = _choices(lv["S"], pes)[2]
        r_ref = R_CHOICES["full" if lv["R"] != "inflex" else "inflex"]
        exact = (f(n_o / full_o) * f(n_p / full_p) * f(n_s / full_s)
                 * f(R_CHOICES[lv["R"]] / r_ref))
        if lv["T"] == "inflex":
            t_hf = 1 / xp.maximum(ref_soft * agn_volume, f(1.0))
            t_wf = [1 / f(float(np.prod(np.asarray(d, np.float64))))
                    for _, d, _, _ in layers]
        elif lv["T"] == "part":
            t_hf = ref_hard / ref_soft
            t_wf = [h for _, h in shares]
        else:
            t_hf = ref_soft / ref_soft
            t_wf = [s for s, _ in shares]
        hf[name] = float(exact * t_hf)
        wf[name] = float(xp.mean(xp.stack([exact * t for t in t_wf])))
    return hf, wf
