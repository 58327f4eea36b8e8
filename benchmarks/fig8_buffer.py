"""Fig 8: buffer-size sensitivity of fully-tile-flexible accelerators.
Runtime improves and W-F rises with buffer size, saturating once most
MnasNet layers fit (~6.4KB in the paper)."""
from __future__ import annotations

import time

import numpy as np

from repro.core import FULLFLEX, HWConfig, get_model, make_variant, search_model

from .common import Table, flexion_reports, ga_budget


def run(print_fn=print):
    layers = get_model("mnasnet")
    cfg = ga_budget(scale=0.5)
    sizes_kb = [1, 2, 4, 8, 16, 64]
    specs = [make_variant("1000", FULLFLEX, hw=HWConfig(buffer_bytes=kb * 1024))
             for kb in sizes_kb]
    probe_layers = layers[::4]
    t = Table("Fig 8 — buffer-size sensitivity (FullFlex-1000, MnasNet)",
              ["buffer_kb", "runtime", "runtime_rel", "W-F(T)"])
    timings = {}

    # W-F of the T axis (the flexible axis in this isolation study): one
    # campaign over all (buffer size, probe layer) rows — each HWConfig
    # samples its C_X reference once
    reports = flexion_reports([(spec, l) for spec in specs
                               for l in probe_layers], 5_000, timings)
    wf_t = {spec.hw.buffer_bytes: float(np.mean(
        [r.per_axis_wf["T"]
         for r in reports[si * len(probe_layers):
                          (si + 1) * len(probe_layers)]]))
        for si, spec in enumerate(specs)}

    t0 = time.time()
    runtimes, wfs = [], []
    for kb, spec in zip(sizes_kb, specs):
        res = search_model(layers, spec, cfg)
        runtimes.append(res.runtime)
        wfs.append(wf_t[spec.hw.buffer_bytes])
        t.add(kb, res.runtime, res.runtime / runtimes[0],
              round(wfs[-1], 4))
    timings["mse_sweep"] = round(time.time() - t0, 6)
    t.show(print_fn)
    return {
        "monotone_runtime": all(runtimes[i + 1] <= runtimes[i] * 1.05
                                for i in range(len(runtimes) - 1)),
        "wf_increases": wfs[-1] > wfs[0],
        "speedup_1k_to_64k": runtimes[0] / runtimes[-1],
        "_phases": timings,
    }
