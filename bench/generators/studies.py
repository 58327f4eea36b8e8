"""Whole fig13 future-proofing studies, back to back.

Each study is ``future_proofing_study(campaign=True)`` on the
configuration's base model, future models, classes and GA budget, with its
H-F and W-F columns, as an architect runs it: the fixed-config designs,
the frozen-design replay, the flexion estimators and the engine sweep of
every (model, variant) pair.  Each study draws a fresh GA seed from the
run's seed and starts with the flexion caches cleared.  The window starts
studies until ``seconds`` have passed and waits for the last one; a traced
run traces the first study.
"""
from __future__ import annotations

import dataclasses
import sys
import time
import traceback

import numpy as np

from lib import check
from lib.spans import wrap_engine


def study_seeds(seed: int):
    """The GA seeds of a run's studies: a numpy stream seeded by the run's
    seed, so the same seed gives the same studies."""
    rng = np.random.default_rng(seed)
    while True:
        yield int(rng.integers(0, 2**31 - 1))


def bucket(n: int, base: int) -> int:
    b = base
    while b < n:
        b *= 2
    return b


def _ga(ctx):
    from repro.core import GAConfig
    return GAConfig(**ctx.config["ga"], pipeline=True,
                    devices=ctx.chips if ctx.chips > 1 else None)


def _sweep_chunks(ctx, classes):
    """(distinct-spec bucket, R-open) of every engine chunk of a study's
    variant sweep: rows go model by model, variant by variant, one per
    distinct layer, in chunks of the engine's row bucket."""
    from repro.core import get_model
    from repro.core.engine import ROW_BUCKET, TABLE_BUCKET
    from repro.core.mapper import plan_model_rows
    rows = [(i, r_open) for m in ctx.config["models"]
            for i, r_open in enumerate(classes)
            for _ in plan_model_rows(get_model(m))[0]]
    out = set()
    for start in range(0, len(rows), ROW_BUCKET):
        chunk = rows[start:start + ROW_BUCKET]
        out.add((bucket(len({i for i, _ in chunk}), TABLE_BUCKET),
                 any(r for _, r in chunk)))
    return out


def warm_engine(ga, hw, combos, n_devices: int) -> None:
    """Run one engine chunk of each (table bucket, R-open) shape on every
    device, with rows outside any study: ``k`` distinct accelerators give
    the bucket, one R-open accelerator the width-scaled program."""
    from repro.core import make_variant
    from repro.core.engine import ROW_BUCKET, EngineRow, run_batched_ga
    from repro.core.workloads import Layer
    layer = Layer("bench-warm", (4, 4, 4, 4, 1, 1))
    pinned = [make_variant(f"{i:04b}", level, hw=hw)
              for level in ("full", "part") for i in range(1, 16)]
    for t_pad, r_open in sorted(combos):
        k = 1 if t_pad <= 8 else t_pad // 2 + 1
        specs = ([make_variant("00001", hw=hw)] if r_open else []) + pinned
        rows = [EngineRow(layer, s, 0) for s in specs[:k]]
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
    ga = _ga(ctx)
    classes = list(c["classes"])
    r_open = [len(cls) == 5 and cls[4] == "1" for cls in classes]
    r_open += [False] * bool(c["include_partflex_1111"])
    warm_engine(ga, hw, _sweep_chunks(ctx, r_open), ctx.chips)
    names = list(dict.fromkeys([c["base_model"], *c["models"]]))
    search_fixed_configs([(get_model(m), FlexSpec(name=f"probe-{m}", hw=hw))
                          for m in names],
                         dataclasses.replace(ga, generations=1))
    future = [layer for m in c["models"] for layer in get_model(m)]
    fx = [make_variant("0000", hw=hw), make_variant("1111", hw=hw)]
    flexion_campaign([(s, None, 0) for s in fx],
                     mc_samples=c["flexion_samples"], seed=0)
    model_flexion_campaign([(s, future) for s in fx], c["flexion_samples"])
    clear_flexion_reference_cache()
    if ctx.spans.enabled:
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


def window(ctx, st):
    from repro.core import future_proofing_study
    from repro.core.flexion_batched import clear_flexion_reference_cache
    c = ctx.config
    seeds = study_seeds(ctx.seed)
    studies, attempted, failed = [], 0, 0
    t0 = time.perf_counter()
    while attempted == 0 or time.perf_counter() - t0 < ctx.seconds:
        attempted += 1
        clear_flexion_reference_cache()
        s = {"seed": next(seeds), "results": {}, "hf": {}, "wf": {},
             "timings": {}}
        t_study = time.perf_counter()
        try:
            future_proofing_study(
                base_model=c["base_model"], future_models=c["models"],
                class_strs=c["classes"], hw=st["hw"],
                cfg=dataclasses.replace(st["ga"], seed=s["seed"]),
                include_partflex_1111=c["include_partflex_1111"],
                campaign=True, timings=s["timings"], flexion=s["hf"],
                wflexion=s["wf"], flexion_samples=c["flexion_samples"],
                results=s["results"])
        except Exception:   # the run reports it as a failed study
            traceback.print_exc()
            failed += 1
            break
        s["wall_s"] = time.perf_counter() - t_study
        studies.append(s)
        # one study is a whole period of the work: tracing more only makes
        # the trace longer to write and to read
        ctx.spans.stop()
    elapsed = time.perf_counter() - t0
    e2e = {"campaign_s": elapsed / len(studies)} if studies else {}
    print(f"[studies] {len(studies)} studies in {elapsed:.3f} s: "
          f"{[(s['seed'], round(s['wall_s'], 3), s['timings']) for s in studies]}",
          file=sys.stderr, flush=True)
    ga = st["ga"]
    return {"attempted": attempted, "failed": failed, "end_to_end": e2e,
            "studies": studies,
            "counters": {"studies": len(studies),
                         "timings": [s["timings"] for s in studies],
                         "evals_per_row": ga.population * ga.generations}}


def _answers(ctx, win):
    """Every answer of the window's studies as ``(model, hard partition,
    ModelResult, engine-searched, tile-flexible)``, with the study's
    accelerator rows; ``None`` where a study answered another set of
    (row, model) cells than the configuration asks for."""
    c = ctx.config
    base_row = f"InFlex0000-{c['base_model']}-Opt"
    rows = {base_row: check.class_levels(base_row),
            "InFlex0000-X-Opt": check.class_levels(base_row)}
    for cls in c["classes"]:
        rows[f"FullFlex{cls}-{c['base_model']}-Opt"] = \
            check.class_levels(f"FullFlex{cls}")
    if c["include_partflex_1111"]:
        rows[f"PartFlex1111-{c['base_model']}-Opt"] = \
            check.class_levels("PartFlex1111")
    answers = []
    for s in win["studies"]:
        if set(s["results"]) != {(r, m) for r in rows for m in c["models"]}:
            return None, rows
        for (row, model), (_, mres) in s["results"].items():
            levels = rows[row]
            engine = row.startswith(("FullFlex", "PartFlex"))
            answers.append((model, levels["T"] == "part", mres, engine,
                            engine and levels["T"] != "inflex"))
    return answers, rows


def readings(ctx, st, win, control: bool = False):
    c = ctx.config
    answers, rows = _answers(ctx, win)
    if answers is None:
        return {"cost_gap": float("inf")}
    layers = check.suite_layers(c["models"])
    flex = 0.0
    for s in win["studies"]:
        flex = max(flex, check.flexion_gap(s["hf"], s["wf"], rows, layers,
                                           c["hw"], c["flexion_samples"],
                                           control))
    return {"cost_gap": check.cost_gap([a[:3] for a in answers], c["hw"],
                                       control),
            "flexion_gap": flex,
            "stalled_share": check.stalled_share(
                [a[2] for a in answers if a[4]]),
            "short_history": check.short_history(
                [a[2] for a in answers if a[3]], c["ga"]["generations"])}
