#!/usr/bin/env python3
"""Record the small trace that ``bench/tests/test_trace.py`` reduces.

    python3 bench/tools/record_trace.py --out <dir>

On a TPU: two engine chunks of a small GA (population 8, 4 generations),
prepared and collected under the benchmark's spans inside a
``bench.window`` span, with a sleep between them that no span covers.
Writes ``<dir>/small.xplane.pb`` and prints each plane's lines with their
event counts and first events.  Exits 3 without a TPU.
"""
import argparse
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    import tempfile

    from jax.profiler import ProfileData

    from lib.harness import NoDevice, init_jax
    from lib.spans import Spans, wrap_engine
    from repro.core import GAConfig, make_variant
    from repro.core.engine import EngineRow, run_batched_ga
    from repro.core.workloads import Layer

    try:
        init_jax(1, True)
    except NoDevice as e:
        print(f"no device: {e}", file=sys.stderr)
        return 3
    cfg = GAConfig(population=8, generations=4)
    rows = [EngineRow(Layer("l", (64, 32, 14, 14, 3, 3)),
                      make_variant("1111"), seed=i) for i in range(3)]
    run_batched_ga(rows, cfg)                      # compile outside
    spans = Spans(True)
    wrap_engine(spans)
    with tempfile.TemporaryDirectory() as tmp:
        spans.start(tmp)
        run_batched_ga(rows, cfg)
        time.sleep(0.05)
        run_batched_ga(rows, cfg)
        spans.stop()
        spans.close()
        path = next(Path(tmp).rglob("*.xplane.pb"))
        Path(args.out).mkdir(parents=True, exist_ok=True)
        shutil.copy(path, Path(args.out) / "small.xplane.pb")
    data = ProfileData.from_file(str(Path(args.out) / "small.xplane.pb"))
    for plane in data.planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  line", line.name, len(evs),
                  [(e.name, e.start_ns, e.duration_ns) for e in evs[:3]])
    return 0


if __name__ == "__main__":
    sys.exit(main())
