#!/usr/bin/env python3
"""Chip smoke test: the DSE campaign, its kernels and the LM stack on a TPU.

    python3 chip_smoke.py              # one chip: every phase below
    python3 chip_smoke.py --chips 4    # four chips: the device-pool campaign

One process drives the chip(s).  Each phase prints one line with its wall
time and the time XLA spent compiling in it (backend compiles and
compile-cache loads, as ``repro.core.tracing`` counts them); a failed phase
prints its traceback and the run goes on to the next, then exits 1.  The last line of
standard output is ``{"ok": true, "device": {...}}`` only when every phase
passed.  Without a TPU the script exits 2 before any phase and prints no
result.

One-chip phases, in order:

  device     JAX must report a TPU as device 0 (it can fall back to the CPU
             when the TPU fails to initialise, so this is checked, not
             assumed).
  campaign   the fig13 future-proofing suite (alexnet frozen, seven models,
             all 32 flexibility classes, H-F and W-F columns) at the paper's
             GA budget, 100 x 100.  Every best genome the chip returns is
             re-evaluated by the cost model on the host CPU device: runtimes
             must agree within COST_RTOL and feasibility flags exactly.
  parity     the same suite at the ``fast`` budget on the chip and on the
             host CPU device, in this process: how many table rows agree
             bit for bit and the largest relative difference of each
             derived metric.  Gated on structure, finiteness and the host
             re-evaluation of the chip's genomes, not on bit-equality.
  replay     ``evaluate_fixed_genome_many`` on the chip (many == solo, and
             the host CPU device within COST_RTOL); ``flexion_campaign``
             with the jax backend on the chip against the numpy backend on
             the host within FLEXION_ATOL; a ``DSEService`` answering 3
             clients at the full budget, every answer equal to a solo
             ``search_campaign`` and the repeat served from its cache.
  kernels    interpret mode off; ``ops.matmul`` at 4096^3 in bf16, f32 and
             int8 in every order, ``ops.attention`` at (16, 4096, 128) bf16
             and ``ops.mamba_scan`` at (1, 4096, 2048, 16) f32 against
             ``kernels/ref.py`` at ``PARITY_TOLS``; then one rank-correlation
             study and one measured ``tune_kernel`` per kind at the autotune
             bench's ``full`` shapes, every lowered config compiled, run and
             checked against the oracle.
  lm         ``run_training("lm-100m", smoke=False)`` for 4 steps on a
             (1, 1) mesh with a fresh checkpoint directory: finite losses.

``--chips 4`` runs only the device-pool campaign: the fig13 suite at the
full budget with ``GAConfig(devices=4)`` and with ``devices=1``; every row
must be bit-identical and every one of the four chips must have run engine
chunks.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import threading
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Relative tolerance of a cost-model runtime evaluated on the chip against
# the same mapping evaluated on the host CPU.  The model is float32
# arithmetic on integer-valued tile and trip counts; the TPU and the CPU
# may round a product, a quotient or a fused multiply-add differently in
# the last place, so the two agree to a few float32 ulps (1.2e-7 each).
# The first v5e run saw at most 1.5e-7 over 12,308 layer mappings.
COST_RTOL = 1e-6
# Absolute tolerance of a flexion fraction, jax float32 on the chip against
# numpy float64 on the host: both count the same integer-valued samples
# against the buffer (exact in float32), and only the float32 mean of
# 20,000 zero/one values rounds, to well below 1e-6.
FLEXION_ATOL = 1e-6

class Phases:
    """Runs phases, prints one timing line each, remembers the failures.
    Each phase runs inside ``repro.core.tracing.recording``; its line
    reports the seconds of the backend compiles (or compile-cache loads)
    made in the phase's thread, the recorder's ``jax:compile_s``."""

    def __init__(self):
        self.failed = []
        self.compile_s = 0.0

    def run(self, name, fn, *args):
        from repro.core import tracing

        t0, rec = time.perf_counter(), {}
        ok = True
        try:
            with tracing.recording(rec):
                fn(*args)
        except Exception:  # the run goes on; the exit code reports it
            ok = False
            self.failed.append(name)
            traceback.print_exc()
        compile_s = rec.get("jax:compile_s", 0.0)
        self.compile_s += compile_s
        print(f"[phase] {name}: {'ok' if ok else 'FAILED'}  "
              f"wall {time.perf_counter() - t0:.1f} s  "
              f"compile {compile_s:.1f} s", flush=True)
        return ok


def _check(cond, msg):
    if not cond:
        raise AssertionError(msg)


# -- the fig13 suite ----------------------------------------------------------

def _suite(cfg, with_flexion=True):
    """The fig13 future-proofing study; returns (table, results, H-F, W-F)."""
    from benchmarks.fig13_futureproof import BASE, CLASSES_5AXIS, MODELS
    from repro.core import clear_flexion_reference_cache, future_proofing_study

    clear_flexion_reference_cache()
    results, flexion, wflexion = {}, {}, {}
    table = future_proofing_study(
        base_model=BASE, future_models=MODELS, class_strs=CLASSES_5AXIS,
        cfg=cfg, campaign=True, results=results,
        flexion=flexion if with_flexion else None,
        wflexion=wflexion if with_flexion else None)
    return table, results, flexion, wflexion


def _geomeans(table):
    from benchmarks.fig13_futureproof import BASE, MODELS
    from repro.core import geomean_speedup
    future = [m for m in MODELS if m != BASE]
    return {row: (geomean_speedup(table, row, future),
                  geomean_speedup(table, row))
            for row in table}


def _host_costs(entries, cpu):
    """Cost-model (runtime, feasible) of each (layer, spec, MapperResult)
    entry's mapping, evaluated on the host CPU device."""
    import jax
    import numpy as np

    from repro.core import evaluate_rows, mapspace_for

    cols = {k: [] for k in ("dims", "stride", "dw", "tiles", "order", "par",
                            "shape", "hp", "bits")}
    for layer, spec, res in entries:
        m = res.mapping
        for k, v in (("dims", layer.dims), ("stride", layer.stride),
                     ("dw", layer.depthwise), ("tiles", m.tiles),
                     ("order", m.order), ("par", m.parallel),
                     ("shape", m.shape),
                     ("hp", mapspace_for(layer, spec).hard_partition),
                     ("bits", m.repr_bits)):
            cols[k].append(v)
    a = {k: np.asarray(v, np.bool_ if k in ("dw", "hp") else np.int32)
         for k, v in cols.items()}
    hw = entries[0][1].hw
    native = 8 * hw.bytes_per_elem
    runtime = np.empty(len(entries), np.float64)
    feasible = np.empty(len(entries), np.bool_)
    with jax.default_device(cpu):
        # native-width rows through the pre-R program, the others scaled
        for sel, with_bits in ((a["bits"] == native, False),
                               (a["bits"] != native, True)):
            if not sel.any():
                continue
            args = [a[k][sel] for k in ("dims", "stride", "dw", "tiles",
                                        "order", "par", "shape", "hp")]
            out = evaluate_rows(*args, hw, a["bits"][sel] if with_bits
                                else None)
            runtime[sel] = np.asarray(out.runtime, np.float64)
            feasible[sel] = np.asarray(out.feasible)
    return runtime, feasible


def _check_on_host(results, cpu, label):
    """Re-evaluate every (row, model) cell's per-layer mappings on the host
    CPU device and hold the chip's runtimes and feasibility to them."""
    import numpy as np

    from repro.core import get_model

    entries = []
    for (_, model), (spec, mres) in results.items():
        layers = get_model(model)
        entries += [(layer, spec, r)
                    for layer, r in zip(layers, mres.per_layer)]
    host_rt, host_feas = _host_costs(entries, cpu)
    chip_rt = np.asarray([r.runtime for _, _, r in entries], np.float64)
    chip_feas = np.asarray([r.feasible for _, _, r in entries])
    rel = np.abs(chip_rt - host_rt) / np.maximum(np.abs(host_rt), 1e-30)
    n_bad = int((rel > COST_RTOL).sum())
    print(f"[{label}] host re-evaluation of {len(entries)} layer mappings: "
          f"max rel diff {rel.max():.3e} (tol {COST_RTOL:g}), "
          f"{int((rel > 0).sum())} not bit-equal, {n_bad} over tol, "
          f"feasibility mismatches {int((chip_feas != host_feas).sum())}",
          flush=True)
    if n_bad:
        worst = int(np.argmax(rel))
        layer, spec, r = entries[worst]
        print(f"[{label}] worst: {layer.name} {layer.dims} spec {spec.name} "
              f"mapping {r.mapping} chip {chip_rt[worst]!r} "
              f"host {host_rt[worst]!r}", flush=True)
    _check(np.array_equal(chip_feas, host_feas),
           "feasibility flags differ between chip and host")
    _check(n_bad == 0, f"{n_bad} runtimes differ from the host beyond "
                       f"{COST_RTOL:g}")


def phase_device(state, want_count):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    state["device"] = {"platform": d0.platform, "kind": d0.device_kind,
                       "count": len(devs)}
    print(f"[device] platform {d0.platform}  kind {d0.device_kind}  "
          f"count {len(devs)}", flush=True)
    _check(d0.platform == "tpu", f"device 0 is {d0.platform!r}, not a TPU")
    _check(len(devs) >= want_count,
           f"{len(devs)} devices, {want_count} needed")
    state["cpu"] = jax.devices("cpu")[0]


def phase_campaign(state):
    import math

    from benchmarks.common import BUDGETS
    cfg = dataclasses.replace(BUDGETS["full"], pipeline=True)
    print(f"[campaign] fig13 suite at population {cfg.population}, "
          f"{cfg.generations} generations; cuts: none", flush=True)
    table, results, flexion, wflexion = _suite(cfg)
    gms = _geomeans(table)
    for row, (gm_future, gm_all) in gms.items():
        if row.startswith("FullFlex"):
            print(f"[campaign] {row}: geomean future {gm_future:.4f}  "
                  f"all {gm_all:.4f}  H-F {flexion[row]:.4f}  "
                  f"W-F {wflexion[row]:.4f}", flush=True)
    _check(all(math.isfinite(v) and v > 0 for cols in table.values()
               for v in cols.values()), "non-finite table entry")
    _check(set(flexion) == set(table) == set(wflexion),
           "flexion columns do not cover the table")
    _check_on_host(results, state["cpu"], "campaign")
    state["full_results"] = results


def phase_parity(state):
    import math

    import jax
    import numpy as np

    from benchmarks.common import BUDGETS
    cfg = dataclasses.replace(BUDGETS["fast"], pipeline=True)
    chip = _suite(cfg)
    with jax.default_device(state["cpu"]):
        host = _suite(cfg)
    t_chip, r_chip, hf_chip, wf_chip = chip
    t_host, _, hf_host, wf_host = host
    _check(set(t_chip) == set(t_host), "row sets differ")
    same = [row for row in t_chip if all(
        np.float64(t_chip[row][m]).tobytes()
        == np.float64(t_host[row][m]).tobytes() for m in t_chip[row])]
    print(f"[parity] fast suite: {len(same)} of {len(t_chip)} rows "
          f"bit-identical chip vs host", flush=True)

    def max_rel(a, b):
        return max(abs(a[k] - b[k]) / max(abs(b[k]), 1e-30) for k in b)

    g_chip = {r: v[0] for r, v in _geomeans(t_chip).items()}
    g_host = {r: v[0] for r, v in _geomeans(t_host).items()}
    cells_chip = {(r, m): v for r, cols in t_chip.items()
                  for m, v in cols.items()}
    cells_host = {(r, m): v for r, cols in t_host.items()
                  for m, v in cols.items()}
    for name, a, b in (("normalized runtime", cells_chip, cells_host),
                       ("geomean speedup (future)", g_chip, g_host),
                       ("H-F", hf_chip, hf_host), ("W-F", wf_chip, wf_host)):
        print(f"[parity] max rel diff {name}: {max_rel(a, b):.3e}",
              flush=True)
    _check(all(math.isfinite(v) for v in cells_chip.values()),
           "non-finite chip entry")
    _check_on_host(r_chip, state["cpu"], "parity")


def _bit_equal(a, b) -> bool:
    return ((a.runtime, a.energy, a.edp) == (b.runtime, b.energy, b.edp)
            and all(x.runtime == y.runtime and x.energy == y.energy
                    and x.mapping == y.mapping and x.history == y.history
                    for x, y in zip(a.per_layer, b.per_layer)))


def phase_replay(state):
    import jax
    import numpy as np

    from benchmarks.common import BUDGETS
    from benchmarks.fig13_futureproof import MODELS
    from repro.core import (evaluate_fixed_genome, evaluate_fixed_genome_many,
                            flexion_campaign, get_model, make_variant,
                            search_campaign)
    from repro.core.flexion_batched import clear_flexion_reference_cache
    from repro.serve import DSEService

    # frozen-design replay: the campaign's InFlex design on every model (an
    # all-InFlex spec pins every gene but the tiles to table index 0)
    results = state["full_results"]
    frozen, _ = results["InFlex0000-alexnet-Opt", "alexnet"]
    genome = np.asarray([*frozen.tile.fixed_tile, 0, 0, 0, 0], np.int32)
    reqs = [(get_model(m), frozen, genome) for m in MODELS]
    many = evaluate_fixed_genome_many(reqs)
    solo = [evaluate_fixed_genome(*r) for r in reqs]
    _check(all(_bit_equal(a, b) for a, b in zip(many, solo)),
           "replay: many != solo")
    _check(all(_bit_equal(a, results["InFlex0000-alexnet-Opt", m][1])
               for a, m in zip(many, MODELS)),
           "replay differs from the campaign's replay row")
    with jax.default_device(state["cpu"]):
        host = evaluate_fixed_genome_many(reqs)
    rel = max(abs(a.runtime - b.runtime) / b.runtime
              for a, b in zip(many, host))
    print(f"[replay] {len(MODELS)} models, max rel diff vs host "
          f"{rel:.3e}", flush=True)
    _check(rel <= COST_RTOL, "replay differs from the host")

    # flexion: jax float32 on the chip against numpy float64 on the host
    specs = [spec for (_, m), (spec, _) in results.items()
             if m == "alexnet" and not spec.name.startswith("probe")]
    rows = ([(s, None, 0) for s in specs]
            + [(s, layer, 0) for s in specs[:8]
               for layer in get_model("mnasnet")[:8]])
    reports = {}
    for backend in ("jax", "numpy"):
        os.environ["REPRO_FLEXION_BACKEND"] = backend
        clear_flexion_reference_cache()
        reports[backend] = flexion_campaign(rows, mc_samples=20_000, seed=0)
    del os.environ["REPRO_FLEXION_BACKEND"]
    diff = max(max(abs(a.hf - b.hf), abs(a.wf - b.wf))
               for a, b in zip(reports["jax"], reports["numpy"]))
    print(f"[flexion] {len(rows)} rows, max abs diff jax-on-chip vs "
          f"numpy-on-host {diff:.3e} (tol {FLEXION_ATOL:g})", flush=True)
    _check(diff <= FLEXION_ATOL, "flexion backends disagree")

    # the DSE service: 3 clients, a shared query, one repeat
    cfg = dataclasses.replace(BUDGETS["full"], pipeline=True)
    layers = get_model("mnasnet")[:6]
    shared = make_variant("1111")
    mine = [make_variant(c) for c in ("1110", "1101", "1011")]
    got = [[None, None] for _ in mine]
    errs = []
    with DSEService() as svc:
        def client(i):
            try:
                for j, spec in enumerate((shared, mine[i])):
                    got[i][j] = svc.query(layers, spec, cfg, timeout=900)
            except BaseException as e:
                errs.append(e)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(mine))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errs:
            raise errs[0]
        before = svc.stats()
        repeat = svc.query(layers, shared, cfg, timeout=900)
        after = svc.stats()
    solo = {spec: search_campaign([(layers, spec)], cfg)[0]
            for spec in (shared, *mine)}
    _check(all(_bit_equal(got[i][0], solo[shared])
               and _bit_equal(got[i][1], solo[mine[i]])
               for i in range(len(mine))), "service != solo campaign")
    _check(_bit_equal(repeat, solo[shared]), "repeat != solo campaign")
    _check(after["rows_dispatched"] == before["rows_dispatched"],
           "the repeat query dispatched rows instead of using the cache")
    print(f"[service] 3 clients + 1 repeat at the full budget: answers equal "
          f"solo campaigns, {after['rows_dispatched']} rows dispatched, "
          f"repeat served from cache", flush=True)


def phase_kernels(state):
    from benchmarks.autotune_bench import N_SAMPLES, SHAPES, TUNE_POP_GENS
    from benchmarks.common import BUDGETS
    from repro.core import (HWConfig, KernelConfig, MeasuredRunner,
                            attention_workload, make_variant, mamba_workload,
                            matmul_workload, parity_check,
                            rank_correlation_study, tune_kernel)
    from repro.core.kernel_bridge import REAL_WIDTH, REAL_WIDTH_BLOCKS
    from repro.kernels import ops

    _check(ops._interpret() is False, "Pallas interpret mode is on")
    wls = {"matmul": matmul_workload(*REAL_WIDTH["matmul"]),
           "attention": attention_workload(*REAL_WIDTH["attention"]),
           "mamba": mamba_workload(*REAL_WIDTH["mamba"])}
    cases = [(wls["matmul"], KernelConfig("matmul", blk, order, bits))
             for bits in (16, 32, 8)
             for order, blk in REAL_WIDTH_BLOCKS["matmul"].items()]
    cases.append((wls["attention"], KernelConfig(
        "attention", REAL_WIDTH_BLOCKS["attention"], "", 16)))
    cases.append((wls["mamba"], KernelConfig(
        "mamba", REAL_WIDTH_BLOCKS["mamba"], "", 32)))
    for wl, cfg in cases:
        t0 = time.perf_counter()
        ok, err = parity_check(wl, cfg)
        print(f"[kernels] {wl.kind}{wl.shape} block {cfg.block} "
              f"{cfg.order or '-'} {cfg.bits}-bit: max abs err {err:.3e} "
              f"{'ok' if ok else 'FAILED'} ({time.perf_counter() - t0:.1f} s)",
              flush=True)
        _check(ok, f"{wl.kind} {cfg} differs from kernels/ref.py")

    spec = make_variant("1100", hw=HWConfig(), fixed_bits=32)
    pop, gens = TUNE_POP_GENS["full"]
    tune_cfg = dataclasses.replace(BUDGETS["full"], population=pop,
                                   generations=gens)
    shapes = SHAPES["full"]
    for kind, wl in (("matmul", matmul_workload(*shapes["matmul"])),
                     ("attention", attention_workload(*shapes["attention"])),
                     ("mamba", mamba_workload(*shapes["mamba"]))):
        runner = MeasuredRunner(repeats=2, warmup=1)
        study = rank_correlation_study(wl, spec, n_samples=N_SAMPLES["full"],
                                       seed=0, runner=runner)
        tuned = tune_kernel(wl, spec, tune_cfg, runner)
        inputs = runner.inputs_for(wl)
        configs = set(study["configs"]) | {tuned.config}
        bad = [c for c in configs if not parity_check(wl, c, inputs)[0]]
        print(f"[kernels] {kind}{wl.shape}: {study['n_configs']} sampled "
              f"configs, spearman {study['spearman']:.3f}, {len(runner.cache)}"
              f" configs compiled and run, tuned {tuned.config.block} "
              f"{tuned.config.order or '-'} {tuned.best_cost * 1e6:.1f} us, "
              f"{len(bad)} parity failures", flush=True)
        _check(study["all_legal"], f"{kind}: an illegal config was lowered")
        _check(not bad, f"{kind}: {bad} differ from kernels/ref.py")


def phase_lm(state):
    import math

    from repro.launch.train import run_training

    with tempfile.TemporaryDirectory() as ckpt:
        result = run_training("lm-100m", smoke=False, steps=4, batch=8,
                              seq=128, mesh_shape=(1, 1), ckpt_dir=ckpt,
                              ckpt_every=1000, log_every=1)
    losses = [float(m["loss"]) for m in result.metrics_history]
    print(f"[lm] lm-100m: {len(losses)} steps, losses "
          f"{' '.join(f'{v:.4f}' for v in losses)}", flush=True)
    _check(len(losses) == 4 and all(math.isfinite(v) for v in losses),
           "non-finite or missing losses")


# -- four chips -----------------------------------------------------------------

def phase_pool(state):
    from benchmarks.common import BUDGETS
    from repro.core import engine

    seen = []
    dispatch = engine._dispatch_chunk

    def recording(*args, **kwargs):
        outputs = dispatch(*args, **kwargs)
        seen.append(next(iter(outputs[0].devices())))
        return outputs

    full = dataclasses.replace(BUDGETS["full"], pipeline=True)
    runs = {}
    for n in (4, 1):
        seen.clear()
        engine._dispatch_chunk = recording
        try:
            t0 = time.perf_counter()
            table, results, _, _ = _suite(
                dataclasses.replace(full, devices=n), with_flexion=False)
            dt = time.perf_counter() - t0
        finally:
            engine._dispatch_chunk = dispatch
        per_dev = {}
        for d in seen:
            per_dev[d.id] = per_dev.get(d.id, 0) + 1
        print(f"[pool] devices={n}: {len(seen)} engine chunks, per device "
              f"{dict(sorted(per_dev.items()))}, suite {dt:.1f} s",
              flush=True)
        runs[n] = (table, results, per_dev)
    (t4, r4, dev4), (t1, r1, _) = runs[4], runs[1]
    same = sum(_bit_equal(r4[k][1], r1[k][1]) for k in r1)
    print(f"[pool] {same} of {len(r1)} (row, model) cells bit-identical, "
          f"table equal: {t4 == t1}", flush=True)
    _check(set(r4) == set(r1) and same == len(r1) and t4 == t1,
           "the four-device campaign differs from one device")
    _check(len(dev4) == 4, f"chunks ran on {len(dev4)} devices, not 4")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    # the host CPU device is the independent reference, so the CPU backend
    # must load next to the TPU when the platforms are pinned
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    state = {}
    phases = Phases()
    if not phases.run("device", phase_device, state, args.chips):
        return 2
    t0 = time.perf_counter()
    if args.chips == 4:
        phases.run("pool", phase_pool, state)
    else:
        phases.run("campaign", phase_campaign, state)
        phases.run("parity", phase_parity, state)
        if "full_results" in state:
            phases.run("replay", phase_replay, state)
        else:
            phases.failed.append("replay (needs the campaign)")
        phases.run("kernels", phase_kernels, state)
        phases.run("lm", phase_lm, state)
    print(f"[total] {time.perf_counter() - t0:.1f} s, compile "
          f"{phases.compile_s:.1f} s, failed: {phases.failed or 'none'}",
          flush=True)
    if phases.failed:
        return 1
    jax.effects_barrier()
    print(json.dumps({"ok": True, "device": state["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
