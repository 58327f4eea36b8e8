"""Whole future-proofing studies of a model with grouped and ragged layers
(``kimi-k2-decode32k``), back to back.

The window is ``studies.py``'s: ``future_proofing_study(campaign=True)``
with its H-F and W-F columns, a fresh GA seed each study from the run's
seed, the flexion caches cleared before each.  Set-up follows
``studies.prepare`` and also warms the programs the layer kinds add: the
GA program with traced grouped flags, and its ragged variant, in every
(table bucket, R-open) shape of the sweep's chunks, packed as the engine
packs them (ragged rows in chunks of their own), so the window compiles
nothing.  The readings are ``studies.py``'s four, with the layer kinds'
plain reference (``reference/kinds.py``) beside ``reference/costmodel.py``
and the model's layers from ``reference/kimi.py``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from generators import studies
from lib import check
from reference import costmodel, flexion, kimi, kinds, zoo

window = studies.window


def _sweep_chunks(ctx, r_open):
    """(distinct-spec bucket, R-open, variant) of every engine chunk of a
    study's variant sweep: rows go model by model, variant by variant, one
    per distinct layer; the engine packs the ragged rows after the others,
    each part in chunks of its row bucket.  ``variant`` is ``ragged``,
    ``grouped`` (a grouped row, no ragged one) or ``plain``."""
    from repro.core import get_model
    from repro.core.engine import ROW_BUCKET, TABLE_BUCKET
    from repro.core.mapper import plan_model_rows
    rows = []
    for m in ctx.config["models"]:
        layers = get_model(m)
        plan = [layers[i] for i in plan_model_rows(layers)[0]]
        rows += [(i, r, layer) for i, r in enumerate(r_open)
                 for layer in plan]
    out = set()
    for part in ([x for x in rows if not x[2].ragged],
                 [x for x in rows if x[2].ragged]):
        for start in range(0, len(part), ROW_BUCKET):
            chunk = part[start:start + ROW_BUCKET]
            kinds_ = {layer.kind for _, _, layer in chunk}
            variant = ("ragged" if "ragged" in kinds_ else
                       "grouped" if "grouped" in kinds_ else "plain")
            out.add((studies.bucket(len({i for i, _, _ in chunk}),
                                    TABLE_BUCKET),
                     any(r for _, r, _ in chunk), variant))
    return out


def warm_engine(ga, hw, combos, groups: int, n_devices: int) -> None:
    """Run one engine chunk of each (table bucket, R-open, variant) shape
    on every device, with rows outside any study: as ``studies.warm_engine``
    does, with a small layer of the variant's kind (``groups`` groups for
    the ragged one, the group axis the study's ragged rows have)."""
    from repro.core import make_variant
    from repro.core.engine import ROW_BUCKET, EngineRow, run_batched_ga
    from repro.core.workloads import Layer, grouped_gemm, ragged_gemm
    layer = {"plain": Layer("bench-warm", (4, 4, 4, 4, 1, 1)),
             "grouped": grouped_gemm("bench-warm", 4, 4, 4, 4),
             "ragged": ragged_gemm("bench-warm", 4, [4] * groups, 4)}
    pinned = [make_variant(f"{i:04b}", level, hw=hw)
              for level in ("full", "part") for i in range(1, 16)]
    for t_pad, r_open, variant in sorted(combos):
        k = 1 if t_pad <= 8 else t_pad // 2 + 1
        specs = ([make_variant("00001", hw=hw)] if r_open else []) + pinned
        rows = [EngineRow(layer[variant], s, 0) for s in specs[:k]]
        if n_devices > 1:
            rows = (rows * ROW_BUCKET)[:ROW_BUCKET] * (n_devices - 1) + rows
        run_batched_ga(rows, ga)


def prepare(ctx):
    from repro.core import (FlexSpec, HWConfig, get_model, make_variant,
                            model_flexion_campaign, search_fixed_configs)
    from repro.core.flexion_batched import (clear_flexion_reference_cache,
                                            flexion_campaign)
    c = ctx.config
    hw = HWConfig(**c["hw"])
    ga = studies._ga(ctx)
    r_open = [len(cls) == 5 and cls[4] == "1" for cls in c["classes"]]
    r_open += [False] * bool(c["include_partflex_1111"])
    future = [layer for m in c["models"] for layer in get_model(m)]
    groups = max([len(layer.group_rows) for layer in future] or [1])
    warm_engine(ga, hw, _sweep_chunks(ctx, r_open), groups, ctx.chips)
    names = list(dict.fromkeys([c["base_model"], *c["models"]]))
    search_fixed_configs([(get_model(m), FlexSpec(name=f"probe-{m}", hw=hw))
                          for m in names],
                         dataclasses.replace(ga, generations=1))
    fx = [make_variant("0000", hw=hw), make_variant("1111", hw=hw)]
    flexion_campaign([(s, None, 0) for s in fx],
                     mc_samples=c["flexion_samples"], seed=0)
    model_flexion_campaign([(s, future) for s in fx], c["flexion_samples"])
    clear_flexion_reference_cache()
    if ctx.spans.enabled:
        from lib.spans import wrap_engine
        from repro.core import dse
        wrap_engine(ctx.spans)
        for attr, name in (("search_fixed_configs", "bench.study.design"),
                           ("evaluate_fixed_genome_many",
                            "bench.study.replay"),
                           ("flexion_campaign", "bench.study.flexion"),
                           ("model_flexion_campaign", "bench.study.flexion"),
                           ("search_campaign", "bench.study.sweep")):
            ctx.spans.wrap(dse, attr, name)
    return {"hw": hw, "ga": ga}


def model_layers(config, model: str):
    """The reference's ``(name, dims, stride, kind, group_rows)`` layers of
    ``model``: this cell's model from ``reference/kimi.py``, the paper's
    from ``reference/zoo.py``."""
    if model == config["name"]:
        return kimi.layers(config)
    return [(n, d, st, "depthwise" if dw else "plain", ())
            for n, d, st, dw in zoo.layers(model)]


def reference_costs(arrays, kind, group_rows, hw, control: bool = False):
    """Runtime and energy of each mapping of the table by the reference of
    its kind, in float64 or, for the control, in bfloat16."""
    xp, dtype = np, np.float64
    if control:
        import jax.numpy as jnp
        xp, dtype = jnp, jnp.bfloat16
    rt, en = np.zeros(len(kind)), np.zeros(len(kind))
    for names in (("plain", "depthwise"), ("grouped",), ("ragged",)):
        idx = np.flatnonzero(np.isin(kind, names))
        if not len(idx):
            continue
        m = {k: v[idx] for k, v in arrays.items()}
        if names[0] == "plain":
            r, e, _ = costmodel.mapping_costs(m, hw, xp, dtype)
        elif names[0] == "grouped":
            r, e, _ = kinds.grouped_costs(m, hw, xp, dtype)
        else:
            r, e, _ = kinds.ragged_costs(m, [group_rows[i] for i in idx],
                                         hw, xp, dtype)
        rt[idx] = np.asarray(r, np.float64)
        en[idx] = np.asarray(e, np.float64)
    return rt, en


def cost_gap(entries, config, control: bool = False) -> float:
    """``check.cost_gap`` over layers of every kind: the widest relative
    gap, over every returned layer mapping and model total, between the
    program's runtime and energy and the float64 reference's; with
    ``control`` the bfloat16 reference stands in for the program."""
    cols = {k: [] for k in ("dims", "stride", "depthwise", "tiles", "order",
                            "par", "shape", "bits", "hard")}
    kind, group_rows, rt, en, spans, totals = [], [], [], [], [], []
    for model, hard, mres in entries:
        layers = model_layers(config, model)
        if len(layers) != len(mres.per_layer):
            return float("inf")
        start = len(rt)
        for (_, dims, stride, k, rows), r in zip(layers, mres.per_layer):
            mp = r.mapping
            for key, v in (("dims", dims), ("stride", stride),
                           ("depthwise", k == "depthwise"),
                           ("tiles", mp.tiles), ("order", mp.order),
                           ("par", mp.parallel), ("shape", mp.shape),
                           ("bits", mp.repr_bits), ("hard", hard)):
                cols[key].append(v)
            kind.append(k)
            group_rows.append(rows)
            rt.append(r.runtime)
            en.append(r.energy)
        spans.append((start, len(rt)))
        totals.append((mres.runtime, mres.energy))
    arrays = {k: np.asarray(v) for k, v in cols.items()}
    kind = np.asarray(kind)
    ref_rt, ref_en = reference_costs(arrays, kind, group_rows, config["hw"])
    rt, en = np.asarray(rt), np.asarray(en)
    if control:
        rt, en = reference_costs(arrays, kind, group_rows, config["hw"],
                                 True)
        totals = [(float(np.sum(rt[a:b])), float(np.sum(en[a:b])))
                  for a, b in spans]
    ref_tot = [(np.sum(ref_rt[a:b]), np.sum(ref_en[a:b])) for a, b in spans]
    return max(check.rel_gap(rt, ref_rt), check.rel_gap(en, ref_en),
               check.rel_gap(totals, ref_tot))


def flexion_columns(rows, layers, hw, n: int, xp=np, dtype=np.float64):
    """``reference.flexion.columns`` over layers of every kind: a layer's
    W-F tile samples are drawn over its tile dims (a ragged layer's
    largest group, one group at a time) and fit with its kind's volumes."""
    f = lambda v: xp.asarray(v).astype(dtype)          # noqa: E731
    buf = hw["buffer_bytes"] // hw["bytes_per_elem"]
    pes = hw["num_pes"]
    ref_soft, ref_hard = flexion.fit_shares(
        flexion.tile_draws(flexion.AGNOSTIC_DIMS, 0, n), 1, False, buf, xp,
        dtype)
    agn_volume = f(float(np.prod(np.asarray(flexion.AGNOSTIC_DIMS,
                                            np.float64))))
    tile_dims = [d[:3] + (1,) + d[4:] if k == "ragged" else d
                 for _, d, _, k, _ in layers]

    def shares(i, stride, k):
        draws = flexion.tile_draws(tile_dims[i], i, n)
        if k == "depthwise":
            return flexion.fit_shares(draws, stride, True, buf, xp, dtype)
        return kinds.fit_shares(draws, stride, k == "grouped", buf, xp,
                                dtype)

    t_open = any(lv["T"] != "inflex" for lv in rows.values())
    fits = [shares(i, st, k) if t_open else None
            for i, (_, _, st, k, _) in enumerate(layers)]
    full_o, full_p, full_s = flexion._choices("full", pes)
    hf, wf = {}, {}
    for name, lv in rows.items():
        r_ref = flexion.R_CHOICES["full" if lv["R"] != "inflex"
                                  else "inflex"]
        exact = (f(flexion._choices(lv["O"], pes)[0] / full_o)
                 * f(flexion._choices(lv["P"], pes)[1] / full_p)
                 * f(flexion._choices(lv["S"], pes)[2] / full_s)
                 * f(flexion.R_CHOICES[lv["R"]] / r_ref))
        if lv["T"] == "inflex":
            t_hf = 1 / xp.maximum(ref_soft * agn_volume, f(1.0))
            t_wf = [1 / f(float(np.prod(np.asarray(d, np.float64))))
                    for d in tile_dims]
        elif lv["T"] == "part":
            t_hf = ref_hard / ref_soft
            t_wf = [h for _, h in fits]
        else:
            t_hf = ref_soft / ref_soft
            t_wf = [s for s, _ in fits]
        hf[name] = float(exact * t_hf)
        wf[name] = float(xp.mean(xp.stack([exact * t for t in t_wf])))
    return hf, wf


def flexion_gap(got_hf, got_wf, rows, layers, hw, n: int,
                control: bool = False) -> float:
    """``check.flexion_gap`` with :func:`flexion_columns`."""
    ref_hf, ref_wf = flexion_columns(rows, layers, hw, n)
    if control:
        import jax.numpy as jnp
        got_hf, got_wf = flexion_columns(rows, layers, hw, n, jnp,
                                         jnp.bfloat16)
    if set(got_hf) != set(ref_hf) or set(got_wf) != set(ref_wf):
        return float("inf")
    names = sorted(ref_hf)
    return max(
        check.rel_gap([got_hf[k] for k in names], [ref_hf[k] for k in names]),
        check.rel_gap([got_wf[k] for k in names], [ref_wf[k] for k in names]))


def readings(ctx, st, win, control: bool = False):
    c = ctx.config
    answers, rows = studies._answers(ctx, win)
    if answers is None:
        return {"cost_gap": float("inf")}
    layers = [layer for m in c["models"] for layer in model_layers(c, m)]
    flex = 0.0
    for s in win["studies"]:
        flex = max(flex, flexion_gap(s["hf"], s["wf"], rows, layers,
                                     c["hw"], c["flexion_samples"], control))
    return {"cost_gap": cost_gap([a[:3] for a in answers], c, control),
            "flexion_gap": flex,
            "stalled_share": check.stalled_share(
                [a[2] for a in answers if a[4]]),
            "short_history": check.short_history(
                [a[2] for a in answers if a[3]], c["ga"]["generations"])}
