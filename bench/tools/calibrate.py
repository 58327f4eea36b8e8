#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, in one process.

    python3 bench/tools/calibrate.py --workload <cell> --seconds <s> \\
        --seeds <n> [<n> ...] [--fault <name>] [--out <file.jsonl>]

For each seed the cell's window runs (``--seconds`` long: one study of a
study cell), then the numbers compared are read twice on the same answers:
for the program, and for the control, the reference computed in bfloat16
in the program's place.  With ``--fault`` a fault of
``bench/lib/faults.py`` is planted under the timed path for every seed.
One JSON line per seed goes to standard output and ``--out``.  Exits 3
without a TPU.
"""
import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def calibrate_seed(gen, cell, config, mix, seed, seconds, fault):
    """One seed's window and readings, as the seed's JSON line."""
    from lib.faults import planted
    from lib.harness import Ctx
    from lib.spans import Spans
    ctx = Ctx(cell=cell, config=config, mix=mix, seed=seed, seconds=seconds,
              chips=cell["chips"], spans=Spans(False))
    t0 = time.perf_counter()
    with planted(fault) if fault else contextlib.nullcontext():
        state = gen.prepare(ctx)
        win = gen.window(ctx, state)
    return {"workload": cell["name"], "seed": seed, "fault": fault,
            "attempted": win["attempted"], "failed": win["failed"],
            "end_to_end": win["end_to_end"],
            "program": gen.readings(ctx, state, win),
            "control": gen.readings(ctx, state, win, control=True),
            "seconds": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    from lib.harness import NoDevice, init_jax, load_cell

    _, cell, config, mix, _, gen = load_cell(args.workload)
    try:
        device, _ = init_jax(cell["chips"], True)
    except NoDevice as e:
        print(f"no device: {e}", file=sys.stderr)
        return 3
    out = open(args.out, "a") if args.out else None
    for seed in args.seeds:
        line = calibrate_seed(gen, cell, config, mix, seed, args.seconds,
                              args.fault)
        line["device"] = device["kind"]
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
