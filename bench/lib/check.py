"""The numbers that decide ``correct``: the program's answers against the
plain reference under ``bench/reference``.

- ``cost_gap``: the widest relative gap, over every returned layer mapping
  and every model total, between the runtime and energy the program
  reports and what the reference computes for the same mapping in float64.
  A feasibility disagreement reads as a gap of about 1 (one side costs
  1e30).
- ``flexion_gap``: the widest relative gap of the H-F and W-F columns.
- ``stalled_share``: the share of searched layer mappings on a tile-flexible
  accelerator whose best objective never improved after the GA's first
  generation.
- ``short_history``: the number of searched layer results whose history
  does not hold one finite best objective for each of the configured
  generations.

The control puts the reference itself, computed in bfloat16, in the
program's place.
"""
from __future__ import annotations

from typing import Iterable, List, Sequence, Tuple

import numpy as np

from reference import costmodel, flexion, zoo

TINY = 1e-30


def rel_gap(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return float("inf")
    if got.size == 0:
        return 0.0
    gap = np.abs(got - want) / np.maximum(np.abs(want), TINY)
    return float(np.nanmax(np.where(np.isnan(gap), np.inf, gap)))


def mapping_table(entries: Iterable[Tuple[str, bool, object]]):
    """Flatten ``(model, hard_partition, ModelResult)`` answers into the
    reference's arrays, the program's per-layer runtime / energy, and each
    answer's layer span.  Layer shapes come from the reference's own zoo;
    an answer whose layer count differs from it is marked broken."""
    cols = {k: [] for k in ("dims", "stride", "depthwise", "tiles", "order",
                            "par", "shape", "bits", "hard")}
    rt, en, spans, totals, broken = [], [], [], [], 0
    for model, hard, mres in entries:
        layers = zoo.layers(model)
        if len(layers) != len(mres.per_layer):
            broken += 1
            continue
        start = len(rt)
        for (_, dims, stride, dw), r in zip(layers, mres.per_layer):
            mp = r.mapping
            for k, v in (("dims", dims), ("stride", stride),
                         ("depthwise", dw), ("tiles", mp.tiles),
                         ("order", mp.order), ("par", mp.parallel),
                         ("shape", mp.shape), ("bits", mp.repr_bits),
                         ("hard", hard)):
                cols[k].append(v)
            rt.append(r.runtime)
            en.append(r.energy)
        spans.append((start, len(rt)))
        totals.append((mres.runtime, mres.energy))
    arrays = {k: np.asarray(v) for k, v in cols.items()}
    return arrays, np.asarray(rt), np.asarray(en), spans, totals, broken


def reference_costs(arrays, hw, control: bool = False):
    """Runtime and energy of the table's mappings by the reference, in
    float64 or, for the control, in bfloat16."""
    if not len(arrays["dims"]):
        return np.zeros(0), np.zeros(0)
    if control:
        import jax.numpy as jnp
        rt, en, _ = costmodel.mapping_costs(arrays, hw, jnp, jnp.bfloat16)
    else:
        rt, en, _ = costmodel.mapping_costs(arrays, hw)
    return np.asarray(rt, np.float64), np.asarray(en, np.float64)


def cost_gap(entries, hw, control: bool = False) -> float:
    """See the module docstring; with ``control`` the bfloat16 reference
    stands in for the program."""
    arrays, rt, en, spans, totals, broken = mapping_table(entries)
    if broken:
        return float("inf")
    ref_rt, ref_en = reference_costs(arrays, hw)
    if control:
        rt, en = reference_costs(arrays, hw, True)
        totals = [(float(np.sum(rt[a:b])), float(np.sum(en[a:b])))
                  for a, b in spans]
    ref_tot = [(np.sum(ref_rt[a:b]), np.sum(ref_en[a:b])) for a, b in spans]
    return max(rel_gap(rt, ref_rt), rel_gap(en, ref_en),
               rel_gap(totals, ref_tot))


def flexion_gap(got_hf, got_wf, rows, layers, hw, n: int,
                control: bool = False) -> float:
    """Widest relative gap of the H-F and W-F columns against the float64
    reference; ``rows`` as :func:`reference.flexion.columns` takes them."""
    ref_hf, ref_wf = flexion.columns(rows, layers, hw, n)
    if control:
        import jax.numpy as jnp
        got_hf, got_wf = flexion.columns(rows, layers, hw, n, jnp,
                                         jnp.bfloat16)
    if set(got_hf) != set(ref_hf) or set(got_wf) != set(ref_wf):
        return float("inf")
    names = sorted(ref_hf)
    return max(rel_gap([got_hf[k] for k in names], [ref_hf[k] for k in names]),
               rel_gap([got_wf[k] for k in names], [ref_wf[k] for k in names]))


def stalled_share(results: Sequence) -> float:
    """Share of the distinct searched layer results in ``results`` (model
    results of tile-flexible accelerators) whose history never improved on
    its first generation."""
    seen = {}
    for mres in results:
        for r in mres.per_layer:
            seen[id(r)] = r
    if not seen:
        return float("nan")
    stalled = sum(1 for r in seen.values()
                  if not r.history or r.history[-1] >= r.history[0])
    return stalled / len(seen)


def short_history(results: Sequence, generations: int) -> int:
    """Distinct searched layer results in ``results`` whose history is not
    ``generations`` finite best objectives: a search cut short."""
    seen = {id(r): r for mres in results for r in mres.per_layer}
    return sum(1 for r in seen.values()
               if len(r.history) != generations
               or not np.all(np.isfinite(r.history)))


def class_levels(name: str) -> dict:
    """Axis levels of an accelerator row named ``FullFlex<cls>-..``,
    ``PartFlex<cls>-..`` or ``InFlex<cls>-..``: a '1' in the class string
    opens that axis (T, O, P, S, then R) at the row's level.  A 4-letter
    class pins R."""
    for prefix, level in (("FullFlex", "full"), ("PartFlex", "part"),
                          ("InFlex", "inflex")):
        if name.startswith(prefix):
            cls = name[len(prefix):].split("-", 1)[0].ljust(5, "0")
            return {ax: (level if bit == "1" else "inflex")
                    for ax, bit in zip("TOPSR", cls)}
    raise ValueError(f"unknown accelerator row {name!r}")


def suite_layers(models: Sequence[str]) -> List[tuple]:
    return [layer for m in models for layer in zoo.layers(m)]
