"""Benchmark runner — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV at the end (us_per_call = wall time
of the whole table/figure reproduction; derived = its headline metric).

  PYTHONPATH=src python -m benchmarks.run                 # everything
  PYTHONPATH=src python -m benchmarks.run table3 fig7     # a subset
  REPRO_BENCH_MODE=fast|default|full                      # GA budgets
  REPRO_DEVICES=N|all|i,j                                 # device pool

Machine-readable perf trajectory:

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  python -m benchmarks.run fig7 fig11 fig13 flexion \
      --devices 4 --service 4 --autotune --json BENCH_mapper.json

runs every selected bench in one ``campaign`` pass (the engine with chunk
pipelining and whole-sweep row sets, with per-phase timings).
``--devices N`` adds a ``campaign-dN`` pass with the campaign's chunks
round-robin sharded over a device pool of N (simulated host devices on CPU
via the ``XLA_FLAGS`` line above; real accelerators otherwise),
``--service N`` adds the DSE service bench (N concurrent clients vs N
sequential campaigns — see docs/serving.md), and ``--autotune`` adds ONE
post-loop pass of the measured kernel-autotune bench (predicted-vs-measured
rank correlation + golden parity + measured GA tuning — see
docs/kernels.md) under its own ``autotune`` label.  ``--json`` writes a
BENCH JSON artifact (per-bench ``us_per_call`` + derived metrics + phases +
a ``device_scaling`` block) so future PRs can diff the derived metrics.

All passes must agree on every derived metric (sharded results are
bit-identical to single-device ones); any mismatch makes the run exit
nonzero so CI gates on it.
"""
from __future__ import annotations

import importlib
import json
import os
import sys
import time
import traceback

from ._compare import derived_equal, public_derived

# name -> (module, headline metric); modules import lazily so the roofline
# dry-run child can start before this process imports JAX
BENCHES = {
    "table3": ("table3_area", "fullflex_overhead_pct"),
    "fig7": ("fig7_tile", "fullflex1000_speedup"),
    "fig8": ("fig8_buffer", "speedup_1k_to_64k"),
    "fig9": ("fig9_order", "fullflex0100_speedup"),
    "fig10": ("fig10_parallelism", "fullflex_speedup_16x64"),
    "fig11": ("fig11_shape", "fullflex_speedup"),
    "fig12": ("fig12_arraysize", "speedup_256_to_1024"),
    "fig13": ("fig13_futureproof", "fullflex1111_geomean_future"),
    "flexion": ("flexion_bench", "partflex1000_hf_T"),
    "roofline": ("roofline", "cells_ok"),
    "bridge": ("bridge_validation", "long_decode_speedup"),
    "service": ("service_bench", "_speedup_vs_sequential"),
    "autotune": ("autotune_bench", "parity_ok"),
}


def _module(name: str):
    return importlib.import_module(f".{BENCHES[name][0]}", __package__)


BENCH_SCHEMA = "repro-bench-mapper/v7"

# benches whose derived metrics are pure functions of the MSE engine or the
# (seed-deterministic) flexion estimators (the golden-parity gate only
# covers these; roofline/bridge read external artifacts, table3 never
# touches the mapper, and autotune measures wall-clock so it runs ONCE
# after the campaign passes, never per pass).  "service" qualifies: its
# gated keys (client/query counts, parity/cache flags, unique row count)
# are load- and placement-independent by the service's bit-parity
# contract.
PARITY_BENCHES = {"fig7", "fig8", "fig9", "fig10", "fig11", "fig12",
                  "fig13", "flexion", "service"}


def _warm_engine() -> None:
    """Compile the engine's programs for the current GA budget outside the
    timed region — us_per_call reports steady-state per-figure cost, not the
    one-time jit (which the persistent XLA cache amortizes anyway).

    Warms every jit family a bench can hit: the engine program, the
    fixed-config objective (at the campaign's padded model-axis shape) and
    the fixed-genome evaluator.  Device-pool passes (``campaign-dN``) warm
    each pool device: the engine program via ``warmup_engine`` and the
    replay evaluator via a pool-sized ``evaluate_fixed_genome`` call."""
    import dataclasses

    from repro.core import (Layer, PARTFLEX, evaluate_fixed_genome,
                            make_variant, search_fixed_config,
                            search_fixed_configs)
    from repro.core.engine import ROW_BUCKET, warmup_engine

    from .common import bench_mode, ga_budget

    cfg = ga_budget()
    tiny = Layer("warmup", (4, 4, 4, 4, 1, 1))
    # the flexion estimators are engine-independent numpy; one draw at the
    # mode's sample budget pays the first-touch (allocator, code paths)
    # outside the timed region so the first pass's flexion phases aren't
    # cold-start inflated
    from repro.core import compute_flexion
    from repro.core.flexion_batched import clear_flexion_reference_cache
    from .flexion_bench import MC_BY_MODE
    compute_flexion(make_variant("1111", PARTFLEX), tiny,
                    mc_samples=MC_BY_MODE[bench_mode()])
    clear_flexion_reference_cache()
    warmup_engine(cfg)    # dispatches to every pool device
    # shared jits (fixed-config objective + batched fixed-genome eval)
    wcfg = dataclasses.replace(cfg, generations=2)
    genome, _ = search_fixed_config([tiny], make_variant("1111"), wcfg)
    # the model-stacked fixed-config program at the campaign's padded
    # model-axis shape: fig13 designs its whole model set in one call, so
    # warm with the same request count (same power-of-two bucket)
    from .fig13_futureproof import MODELS
    search_fixed_configs([([tiny], make_variant("1111"))] * len(MODELS),
                         wcfg)
    from repro.core.device_pool import default_pool
    pool = default_pool()
    if pool is not None and len(pool) > 1:
        # replay chunks round-robin over the pool: one ROW_BUCKET chunk per
        # device warms each device's evaluate_rows executable
        evaluate_fixed_genome([tiny] * (ROW_BUCKET * len(pool)),
                              make_variant("1111"), genome)


def _run_once(names):
    """Run the selected benches once; returns (csv_rows, results, failed)."""
    csv_rows = []
    results = {}
    failed = 0
    for name in names:
        headline = BENCHES[name][1]
        t0 = time.time()
        try:
            derived = _module(name).run()
            results[name] = derived
            dt_us = (time.time() - t0) * 1e6
            csv_rows.append((name, dt_us, derived.get(headline)))
        except Exception as e:  # noqa: BLE001
            failed += 1
            traceback.print_exc()
            csv_rows.append((name, (time.time() - t0) * 1e6,
                             f"ERROR:{type(e).__name__}"))
    return csv_rows, results, failed


def _speedup_row(rows_a, rows_b):
    speedup = {}
    total_a = total_b = 0.0
    for (name, us_a, _), (_, us_b, _) in zip(rows_a, rows_b):
        speedup[name] = round(us_a / max(us_b, 1.0), 2)
        total_a += us_a
        total_b += us_b
    speedup["total"] = round(total_a / max(total_b, 1.0), 2)
    return speedup


def _bench_json(engine_rows, engine_results, devices=None):
    """BENCH artifact: per-pass per-bench us_per_call + derived metrics
    (+ campaign phase timings) under ``engines``, and — when a
    ``--devices`` pass ran — a ``device_scaling`` block recording the pool
    size and the campaign → sharded-campaign speedup."""
    from .common import bench_mode
    doc = {
        "schema": BENCH_SCHEMA,
        "bench_mode": bench_mode(),
        "created_unix": int(time.time()),
        "warmup": True,   # per-pass jit warmup runs before the timed loop
        "engines": {},
    }
    for engine, rows in engine_rows.items():
        entry = {}
        for name, us, _ in rows:
            derived = engine_results[engine].get(name, {})
            cell = {"us_per_call": round(us, 1),
                    "derived": public_derived(derived)}
            if "_phases" in derived:
                cell["phases"] = {k: round(v * 1e6, 1)   # us, like us_per_call
                                  for k, v in derived["_phases"].items()}
            # v6: service load metrics ride along as cell columns — real
            # data in the artifact, but outside "derived" so the diff gate
            # never compares machine-dependent throughput
            if "_speedup_vs_sequential" in derived:
                cell["speedup_vs_sequential"] = \
                    derived["_speedup_vs_sequential"]
            if "_throughput_qps" in derived:
                cell["throughput_qps"] = derived["_throughput_qps"]
            # v7: autotune's machine-dependent raw numbers (correlations,
            # tuned/default timings) ride along as a cell column outside
            # "derived" so the diff gate never compares them
            if "_rank_corr_matmul" in derived:
                cell["measured"] = {k[1:]: v for k, v in derived.items()
                                    if k.startswith("_")}
            entry[name] = cell
        doc["engines"][engine] = entry
    if devices:
        label = f"campaign-d{devices}"
        import jax
        available = len(jax.local_devices())
        try:
            requested = int(devices)
        except ValueError:
            requested = devices          # "all" / explicit index list
        scaling = {"pass": label, "devices_requested": requested,
                   "devices_available": available}
        if {label, "campaign"} <= set(engine_rows):
            scaling["speedup_campaign_over_devices"] = _speedup_row(
                engine_rows["campaign"], engine_rows[label])
        doc["device_scaling"] = scaling
    return doc


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    # the roofline bench reads dry-run records; generating them starts a
    # JAX child, which must happen before this process imports JAX
    if "roofline" in argv or not any(a in BENCHES for a in argv):
        _module("roofline").ensure_some_records()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    json_path = None
    autotune = False
    devices = None
    service_clients = None
    rest = []
    it = iter(argv)
    for a in it:
        if a in ("--json", "--devices", "--service"):
            value = next(it, None)
            if value is None:
                print(f"error: {a} expects a value", file=sys.stderr)
                return 2
            if a == "--json":
                json_path = value
            elif a == "--service":
                # N concurrent DSE-service clients; adds the "service"
                # bench (concurrent clients vs sequential campaigns)
                try:
                    service_clients = int(value)
                    if service_clients < 1:
                        raise ValueError(value)
                except ValueError:
                    print(f"error: --service expects a positive client "
                          f"count, got {value!r}", file=sys.stderr)
                    return 2
            else:
                # same grammar as REPRO_DEVICES: count | "all" | i,j indices
                from repro.dist.pool import parse_device_spec
                try:
                    if parse_device_spec(value) is None:
                        raise ValueError("empty device spec")
                except ValueError as e:
                    print(f"error: --devices {value!r}: {e}",
                          file=sys.stderr)
                    return 2
                devices = value.strip()
        elif a == "--autotune":
            autotune = True
        else:
            rest.append(a)
    # autotune is opt-in (--autotune or named explicitly): it measures real
    # kernel wall-clock, so a plain `benchmarks.run` stays model-only
    names = ([a for a in rest if a in BENCHES]
             or [n for n in BENCHES if n != "autotune"])
    if "autotune" in names:
        autotune = True
        names.remove("autotune")
    if service_clients is not None:
        os.environ["REPRO_SERVICE_CLIENTS"] = str(service_clients)
        if "service" not in names:
            names.append("service")
    prev_devices = os.environ.get("REPRO_DEVICES")
    passes = ["campaign"]
    if prev_devices and devices is None:
        # a plain `REPRO_DEVICES=N python -m benchmarks.run` IS a sharded
        # run (the per-pass env setup below would otherwise clear the pool)
        passes = [f"campaign-d{prev_devices}"]
        devices = prev_devices.strip()   # device_scaling block rides along
    elif devices is not None:
        passes.append(f"campaign-d{devices}")

    engine_rows = {}
    engine_results = {}
    failed = 0
    for label in passes:
        if "-d" in label:    # campaign-dN: shard chunks over N devices
            os.environ["REPRO_DEVICES"] = label.split("-d", 1)[1]
        else:
            os.environ.pop("REPRO_DEVICES", None)
        # a warmup that fails would leave the compile inside the timed
        # pass, so it fails the run
        _warm_engine()
        rows, results, nfail = _run_once(names)
        engine_rows[label] = rows
        engine_results[label] = results
        failed += nfail
    if prev_devices is None:
        os.environ.pop("REPRO_DEVICES", None)
    else:
        os.environ["REPRO_DEVICES"] = prev_devices

    # measured-runtime autotune pass: runs ONCE under its own label after
    # the campaign passes (wall-clock objective — per-pass repeats would
    # just re-measure), so the pass list, parity gate, and
    # results/bench_results.json are untouched
    if autotune:
        rows, results, nfail = _run_once(["autotune"])
        engine_rows["autotune"] = rows
        engine_results["autotune"] = results
        failed += nfail

    # golden-parity gate: every pass must derive identical metrics on the
    # engine-driven benches.  A mismatch is a real engine bug (sharded
    # results promise to be bit-identical), so it must fail the run, not
    # just print.
    base = passes[0]
    for label in passes[1:]:
        for name in names:
            if name not in PARITY_BENCHES:
                continue
            if (name not in engine_results[base]
                    or name not in engine_results[label]):
                continue   # the pass crashed — already counted, not a
                           # parity bug
            da = public_derived(engine_results[base][name])
            db = public_derived(engine_results[label][name])
            if not derived_equal(da, db):
                failed += 1
                print(f"PARITY MISMATCH {name}: [{base}] {da} != "
                      f"[{label}] {db}", file=sys.stderr)

    os.makedirs("results", exist_ok=True)
    with open("results/bench_results.json", "w") as f:
        json.dump(engine_results[passes[-1]], f, indent=2, default=str)
    if json_path:
        with open(json_path, "w") as f:
            json.dump(_bench_json(engine_rows, engine_results,
                                  devices=devices), f, indent=2,
                      default=str)
        print(f"\nwrote {json_path}")

    for engine, erows in engine_rows.items():
        tag = f"[{engine}] " if len(engine_rows) > 1 else ""
        print(f"\n{tag}name,us_per_call,derived")
        for name, us, derived in erows:
            print(f"{name},{us:.0f},{derived}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
