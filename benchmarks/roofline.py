"""Roofline table from the dry-run artifacts (results/dryrun.jsonl).

Prints, per (arch x shape) on the single-pod mesh: the three roofline terms,
the dominant bottleneck, MODEL_FLOPS/HLO_FLOPs, and per-device memory.  The
dry-run itself runs in a separate, CPU-only process (512 fake host devices);
this bench only *reads* its records, so `-m benchmarks.run` stays
single-device.  `ensure_some_records` starts that process, and refuses to
once this process has imported JAX: `benchmarks.run` calls it first."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, List

DRYRUN_PATH = os.environ.get("REPRO_DRYRUN_JSONL", "results/dryrun.jsonl")


def load_records(path: str = DRYRUN_PATH) -> List[Dict]:
    if not os.path.exists(path):
        return []
    recs = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            r = json.loads(line)
            recs[(r["arch"], r["shape"], r["mesh"])] = r  # latest wins
    return list(recs.values())


def ensure_some_records(print_fn=print) -> List[Dict]:
    recs = load_records()
    if recs:
        return recs
    if "jax" in sys.modules:
        raise RuntimeError(
            "no dry-run records, and this process has imported JAX: start "
            "the dry-run before importing JAX (benchmarks.run does)")
    # generate one representative cell so the bench is self-contained
    print_fn("[roofline] no dry-run records found; running one cell "
             "(gemma-2b x train_4k) in a CPU-only subprocess...")
    env = dict(os.environ, PYTHONPATH="src")
    subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "gemma-2b",
         "--shape", "train_4k", "--out", DRYRUN_PATH],
        env=env, check=True, timeout=1800)
    return load_records()


def run(print_fn=print):
    from .common import Table

    recs = ensure_some_records(print_fn)
    single = [r for r in recs if r["mesh"] == "16x16"]
    multi = [r for r in recs if r["mesh"] == "2x16x16"]

    t = Table("Roofline (single-pod 16x16, per-device terms)",
              ["arch", "shape", "status", "compute_ms", "memory_ms",
               "collective_ms", "dominant", "useful", "args_GB", "temp_GB"])
    n_ok = n_skip = n_err = 0
    for r in sorted(single, key=lambda r: (r["arch"], r["shape"])):
        if r["status"] == "skipped":
            n_skip += 1
            t.add(r["arch"], r["shape"], "SKIP(full-attn@500k)", "-", "-",
                  "-", "-", "-", "-", "-")
            continue
        if r["status"] != "ok" or "roofline" not in r:
            n_err += r["status"] != "ok"
            t.add(r["arch"], r["shape"], r["status"], "-", "-", "-", "-",
                  "-", "-", "-")
            continue
        n_ok += 1
        rf = r["roofline"]
        mem = r["memory"]
        t.add(r["arch"], r["shape"], "ok",
              round(rf["compute_s"] * 1e3, 2),
              round(rf["memory_s"] * 1e3, 2),
              round(rf["collective_s"] * 1e3, 2),
              rf["dominant"],
              round(r.get("useful_compute_fraction", 0), 3),
              round((mem["argument_bytes"] or 0) / 1e9, 2),
              round((mem["temp_bytes"] or 0) / 1e9, 2))
    t.show(print_fn)

    if multi:
        t2 = Table("Multi-pod proof (2x16x16): compile + memory",
                   ["arch", "shape", "status", "compile_s", "args_GB",
                    "temp_GB"])
        for r in sorted(multi, key=lambda r: (r["arch"], r["shape"])):
            if r["status"] == "ok":
                mem = r["memory"]
                t2.add(r["arch"], r["shape"], "ok", r.get("compile_s"),
                       round((mem["argument_bytes"] or 0) / 1e9, 2),
                       round((mem["temp_bytes"] or 0) / 1e9, 2))
            else:
                t2.add(r["arch"], r["shape"], r["status"], "-", "-", "-")
        t2.show(print_fn)

    return {"cells_ok": n_ok, "cells_skipped": n_skip, "cells_error": n_err,
            "multi_pod_ok": sum(r["status"] == "ok" for r in multi)}
