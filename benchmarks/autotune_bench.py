"""Autotune BENCH pass: predicted-vs-measured rank correlation + measured
GA tuning per kernel kind (matmul / attention / mamba).

The model-to-measurement loop the kernel bridge closes, as a gated artifact:

  * rank correlation — sample genomes, lower each to its kernel config,
    and Spearman-correlate the cost model's predicted runtime with measured
    interpret-mode wall-clock per distinct config.  The correlation's SIGN
    and the deterministic config counts are diff-gated; the raw correlation
    values and timings are machine-dependent "_" sidecars.
  * golden parity — every measured config is also executed against the
    kernels/ref oracle (``parity_ok`` gates the whole pass).
  * measured tuning — ``tune_kernel`` runs the GA with wall-clock as the
    objective, reusing the study's timing cache; the tuned config must be
    legal (``tuned_legal_ok``) and its speedup over the max-block default
    config rides along as a sidecar.

Derived keys (schema v7):
  parity_ok, tuned_legal_ok, configs_measured,
  rank_corr_positive_{matmul,attention,mamba}      (diff-gated)
  _rank_corr_*, _tuned_us_*, _default_us_*, _tuned_speedup_*   (sidecars)
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .common import BUDGETS, Table, bench_mode

# Workload shapes and budgets per REPRO_BENCH_MODE — small enough that the
# per-distinct-config jit compile (interpret mode) keeps the pass in CI
# smoke range, large enough that block choice moves the measured time.
SHAPES = {
    "fast": {"matmul": (128, 128, 128), "attention": (2, 128, 32),
             "mamba": (1, 64, 32, 8)},
    "default": {"matmul": (256, 256, 128), "attention": (4, 256, 64),
                "mamba": (2, 128, 64, 16)},
    "full": {"matmul": (512, 512, 256), "attention": (4, 512, 64),
             "mamba": (2, 256, 128, 16)},
}
N_SAMPLES = {"fast": 12, "default": 16, "full": 24}
TUNE_POP_GENS = {"fast": (10, 4), "default": (16, 6), "full": (24, 8)}


def _workloads(mode: str):
    from repro.core import (attention_workload, mamba_workload,
                            matmul_workload)
    shapes = SHAPES[mode]
    return {
        "matmul": matmul_workload(*shapes["matmul"]),
        "attention": attention_workload(*shapes["attention"]),
        "mamba": mamba_workload(*shapes["mamba"]),
    }


def run(print_fn=print):
    from repro.core import (HWConfig, MeasuredRunner, config_legal,
                            lower_mapping, make_variant, mapspace_for,
                            parity_check, tune_kernel)
    from repro.core.kernel_bridge import rank_correlation_study

    mode = bench_mode()
    hw = HWConfig()
    # T/O open at a pinned fp32 width: exactly the axes the kernels realize
    # (P/S are mesh-level; an open R would mix executed dtypes into one
    # correlation, and bf16 emulation speed on CPU is not what the model
    # predicts)
    spec = make_variant("1100", hw=hw, fixed_bits=32)
    wls = _workloads(mode)
    n_samples = N_SAMPLES[mode]
    pop, gens = TUNE_POP_GENS[mode]
    tune_cfg = dataclasses.replace(BUDGETS[mode], population=pop,
                                   generations=gens)

    derived = {
        "parity_ok": False, "tuned_legal_ok": False,
        "configs_measured": 0,
        "rank_corr_positive_matmul": False,
        "rank_corr_positive_attention": False,
        "rank_corr_positive_mamba": False,
    }
    if not MeasuredRunner().available():
        raise RuntimeError(
            "the autotune bench measures Pallas kernels, and REPRO_NO_PALLAS "
            "turns measurement off; unset it to run this bench")

    t = Table(f"autotune: predicted vs measured ({mode})",
              ["kernel", "configs", "spearman", "tuned config",
               "tuned_us", "default_us", "speedup", "parity"])

    parity_all = True
    legal_all = True
    configs_total = 0
    for kind, wl in wls.items():
        runner = MeasuredRunner(repeats=2, warmup=1)
        study = rank_correlation_study(wl, spec, n_samples=n_samples,
                                       seed=0, runner=runner)
        corr = study["spearman"]
        configs_total += study["n_configs"]
        derived[f"rank_corr_positive_{kind}"] = bool(corr > 0.0)
        derived[f"_rank_corr_{kind}"] = round(corr, 4)

        # golden parity of every measured config (one shared input set)
        inputs = runner.inputs_for(wl)
        kind_parity = all(parity_check(wl, kcfg, inputs)[0]
                          for kcfg in study["configs"])

        # measured-objective tuning, reusing the study's timing cache
        tuned = tune_kernel(wl, spec, tune_cfg, runner)
        legal_all &= config_legal(wl, tuned.config)
        kind_parity &= parity_check(wl, tuned.config, inputs)[0]
        parity_all &= kind_parity

        # max-block default (full-dim tiles) as the speedup baseline
        space = mapspace_for(wl.layer, spec)
        default_cfg = lower_mapping(wl, space.decode(
            space.clip(np.concatenate([space.dims,
                                       [0, 0, 0, 0]])[None, :])[0]))
        default_s = runner.measure(wl, default_cfg)
        derived[f"_tuned_us_{kind}"] = round(tuned.best_cost * 1e6, 1)
        derived[f"_default_us_{kind}"] = round(default_s * 1e6, 1)
        derived[f"_tuned_speedup_{kind}"] = round(
            default_s / max(tuned.best_cost, 1e-12), 2)
        t.add(kind, study["n_configs"], round(corr, 3),
              f"{tuned.config.block} {tuned.config.order}".strip(),
              round(tuned.best_cost * 1e6, 1), round(default_s * 1e6, 1),
              derived[f"_tuned_speedup_{kind}"], kind_parity)

    derived["parity_ok"] = bool(parity_all)
    derived["tuned_legal_ok"] = bool(legal_all)
    derived["configs_measured"] = int(configs_total)
    t.show(print_fn)
    return derived
