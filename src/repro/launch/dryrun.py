import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# ^ MUST precede every other import (jax locks device count on first init).
# A compile-only tool on host devices: it never opens an accelerator.
"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell with 512 placeholder host devices, and extract the roofline terms
from the compiled artifacts.

  python -m repro.launch.dryrun --arch gemma-2b --shape train_4k [--multipod]
  python -m repro.launch.dryrun --all --out results/dryrun.jsonl

Two compiles per single-pod cell:

  PROOF  — the production config (scanned layers, remat): proves the
           sharding lowers + compiles and yields memory_analysis().
  COST   — HLO cost analysis counts while-loop bodies ONCE (not x trip
           count), so exact FLOPs/bytes/collective-bytes come from *unrolled*
           lowerings at depth L=1 and L=2 (layers are homogeneous), linearly
           extrapolated to the full depth: C(L) = C(1) + (L-1)·ΔC.

Multi-pod cells run the PROOF only (the roofline table is single-pod).
Hardware: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
"""
import argparse
import json
import re
import sys
import time
import traceback
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .hloutil import (HBM_BW, ICI_BW, ICI_LINKS, PEAK_FLOPS, _DTYPE_BYTES,
                      collective_bytes, roofline_terms)

# --------------------------------------------------------------------------
# lowering one cell
# --------------------------------------------------------------------------

def _lower(cfg, shape, mesh):
    from ..configs.shapes import batch_specs, cache_specs
    from ..launch.steps import (default_optimizer, jit_prefill_step,
                                jit_serve_step, jit_train_step, state_specs)
    from ..models import init_params

    if shape.kind == "train":
        opt = default_optimizer(cfg)
        bsp = batch_specs(cfg, shape)
        fn, _, _ = jit_train_step(cfg, opt, mesh, bsp)
        return fn.lower(state_specs(cfg, opt), bsp)
    p_spec = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    if shape.kind == "prefill":
        bsp = batch_specs(cfg, shape)
        csp = cache_specs(cfg, shape)
        fn, _, _ = jit_prefill_step(cfg, mesh, bsp, shape.global_batch,
                                    shape.seq_len)
        return fn.lower(p_spec, bsp, csp)
    # decode
    long_ctx = shape.seq_len >= 2 ** 19
    csp = cache_specs(cfg, shape)
    fn, _, _ = jit_serve_step(cfg, mesh, shape.global_batch, shape.seq_len,
                              long_context=long_ctx)
    tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    return fn.lower(p_spec, tok, csp)


def _cost_cfg(cfg, n_units: int):
    """Reduced-depth, fully-unrolled clone for exact HLO cost analysis.
    SSM chunk size is raised so long sequences don't unroll into hundreds of
    chunk steps (chunking is FLOPs-neutral; compile time is not)."""
    kw = dict(scan_layers=False, unroll_scans=True,
              ssm_chunk=max(cfg.ssm_chunk, 2048))
    if cfg.block == "encdec":
        kw.update(enc_layers=n_units, dec_layers=n_units, n_layers=n_units)
    elif cfg.block == "mamba2_hybrid":
        kw.update(n_layers=n_units * cfg.hybrid_period)
    else:
        kw.update(n_layers=n_units)
    return cfg.replace(**kw)


def _extract(compiled) -> Tuple[float, float, Dict[str, float]]:
    ca = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    return (float(ca.get("flops", 0.0)),
            float(ca.get("bytes accessed", 0.0)), coll)


def _units(cfg) -> int:
    if cfg.block == "encdec":
        return cfg.dec_layers
    if cfg.block == "mamba2_hybrid":
        return cfg.n_layers // cfg.hybrid_period
    return cfg.n_layers


def extrapolated_cost(cfg, shape, mesh) -> Dict:
    """Compile unrolled depth-1 and depth-2 clones; extrapolate to full depth."""
    c1 = _lower(_cost_cfg(cfg, 1), shape, mesh).compile()
    f1, b1, k1 = _extract(c1)
    c2 = _lower(_cost_cfg(cfg, 2), shape, mesh).compile()
    f2, b2, k2 = _extract(c2)
    n = _units(cfg)

    def ext(v1, v2):
        return v1 + (n - 1) * (v2 - v1)

    coll = {key: ext(k1.get(key, 0.0), k2.get(key, 0.0))
            for key in set(k1) | set(k2)}
    return {"flops": ext(f1, f2), "hbm_bytes": ext(b1, b2),
            "collectives": coll,
            "depth_points": {"1": {"flops": f1, "bytes": b1},
                             "2": {"flops": f2, "bytes": b2}},
            "units_extrapolated_to": n}


# --------------------------------------------------------------------------
# cell driver
# --------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, multi_pod: bool,
             verbose: bool = True, skip_cost: bool = False,
             overrides: Optional[Dict] = None,
             mesh_shape: Optional[Tuple[int, int]] = None,
             tag: str = "") -> Dict:
    """One dry-run cell.  `overrides` (ModelConfig.replace kwargs) and
    `mesh_shape` (dp, tp) are the §Perf hillclimbing knobs — they let an
    experiment re-lower the same cell under a different mapping."""
    from ..configs import SHAPES, applicable, get_config, \
        model_flops_per_step
    from ..launch.mesh import make_mesh, make_production_mesh

    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    ok, why = applicable(cfg, shape)
    mesh_name = ("2x16x16" if multi_pod else
                 (f"{mesh_shape[0]}x{mesh_shape[1]}" if mesh_shape
                  else "16x16"))
    rec: Dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    if tag:
        rec["tag"] = tag
    if overrides:
        rec["overrides"] = {k: str(v) for k, v in overrides.items()}
    if not ok:
        rec.update(status="skipped", reason=why)
        if verbose:
            print(f"[{arch} x {shape_name}] SKIP: {why}")
        return rec

    mesh = (make_mesh(mesh_shape, ("data", "model")) if mesh_shape
            else make_production_mesh(multi_pod=multi_pod))
    n_chips = mesh.devices.size
    t0 = time.time()
    try:
        # ---- PROOF: production config (scan+remat) compiles & fits --------
        compiled = _lower(cfg, shape, mesh).compile()
        t_proof = time.time() - t0
        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
            "output_bytes": getattr(ma, "output_size_in_bytes", None),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
        }
        mem["peak_bytes"] = ((mem["argument_bytes"] or 0)
                             + (mem["temp_bytes"] or 0))
        rec.update(status="ok", chips=n_chips, compile_s=round(t_proof, 1),
                   memory=mem, memory_analysis_str=str(ma))

        # ---- COST: unrolled depth-1/2 clones, extrapolated -----------------
        if not multi_pod and not skip_cost:
            t1 = time.time()
            cost = extrapolated_cost(cfg, shape, mesh)
            rec["cost_compile_s"] = round(time.time() - t1, 1)
            terms = roofline_terms(cost["flops"], cost["hbm_bytes"],
                                   cost["collectives"].get("total", 0.0))
            mflops = model_flops_per_step(cfg, shape) / n_chips
            rec.update(per_device=cost, roofline=terms,
                       model_flops_per_device=mflops,
                       useful_compute_fraction=(
                           mflops / cost["flops"] if cost["flops"] else 0.0))
        if verbose:
            msg = (f"[{arch} x {shape_name} @ {rec['mesh']}] "
                   f"proof {t_proof:.0f}s  "
                   f"args={mem['argument_bytes']/1e9:.2f}GB "
                   f"temp={(mem['temp_bytes'] or 0)/1e9:.2f}GB")
            if "roofline" in rec:
                t = rec["roofline"]
                msg += (f"  | compute {t['compute_s']*1e3:.2f}ms "
                        f"memory {t['memory_s']*1e3:.2f}ms "
                        f"collective {t['collective_s']*1e3:.2f}ms "
                        f"dominant={t['dominant']} "
                        f"useful={rec['useful_compute_fraction']:.2f}")
            print(msg)
    except Exception as e:  # noqa: BLE001 — report failures as data
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        if verbose:
            print(f"[{arch} x {shape_name} @ {rec['mesh']}] FAILED: {e}")
    return rec


def main(argv=None):
    from ..configs import ASSIGNED, SHAPES

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-cost", action="store_true",
                    help="proof compile only (no unrolled cost extraction)")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    ap.add_argument("--override", action="append", default=[],
                    help="hillclimb knob: key=value ModelConfig override")
    ap.add_argument("--mesh-shape", default=None,
                    help="hillclimb knob: dpxtp, e.g. 1x256")
    ap.add_argument("--tag", default="", help="label for this variant")
    args = ap.parse_args(argv)
    from .compile_cache import enable_compile_cache
    enable_compile_cache()

    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        if v in ("true", "false"):
            overrides[k] = v == "true"
        else:
            try:
                overrides[k] = int(v)
            except ValueError:
                try:
                    overrides[k] = float(v)
                except ValueError:
                    overrides[k] = v
    mesh_shape = (tuple(int(x) for x in args.mesh_shape.split("x"))
                  if args.mesh_shape else None)

    cells = ([(a, s) for a in ASSIGNED for s in SHAPES] if args.all
             else [(args.arch, args.shape)])
    records = []
    for arch, shape in cells:
        rec = run_cell(arch, shape, args.multipod, skip_cost=args.skip_cost,
                       overrides=overrides or None, mesh_shape=mesh_shape,
                       tag=args.tag)
        records.append(rec)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    n_ok = sum(r["status"] == "ok" for r in records)
    n_skip = sum(r["status"] == "skipped" for r in records)
    n_err = sum(r["status"] == "error" for r in records)
    print(f"dry-run: {n_ok} ok, {n_skip} skipped, {n_err} failed")
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())
