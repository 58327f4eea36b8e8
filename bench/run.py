#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``<cell>`` is a ``workloads`` entry of ``BENCHMARK.json``.  The run sets up
(imports, device, compile cache, the cell's inputs from the seed, warm-up
of every program shape the window uses), measures for ``--seconds``, then
checks the answers of the window against the plain reference.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  The last line of standard output is one JSON object; the numbers
compared, each with its limit, are the last lines of standard error and the
last key of that object.  Without a TPU, or with fewer chips than the cell
asks for, the run exits 3 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent


def _finite(obj):
    """JSON has no inf or nan: a compared number that is not finite is
    printed as the largest float, which fails any limit."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return sys.float_info.max
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_finite(v) for v in obj]
    return obj


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    from lib.harness import NoDevice, run_cell
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), T_START)
    except NoDevice as e:
        print(f"no device: {e}", file=sys.stderr)
        return 3
    print(json.dumps(_finite(out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
