"""Golden-metrics regression harness (ISSUE 3).

The committed ``BENCH_mapper.json`` pins the fast-mode fig7/fig13 derived
paper metrics plus the flexion pass's estimator invariants.  These tests
re-run the benches twice — on the engine (the ``campaign`` path every bench
runs) and with the engine replaced by the per-layer reference GA
(tests/_reference_ga.py) — and assert

  * the two paths agree with each other *bit-identically* (the engine's
    golden-parity contract; same process, same machine, no excuses), and
  * each path reproduces the committed anchor values (floats at rel 1e-6 —
    the same cross-machine slack CI's ``scripts/diff_bench.py`` gate uses,
    absorbing XLA CPU codegen differences between the anchor machine and
    the runner; on the anchor machine the match is in fact bit-exact).

Any drift in the cost model, GA operators, engine batching, chunk
pipelining or campaign packing trips this before it can corrupt the perf
trajectory.
"""
import importlib
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:          # benchmarks/ lives at the repo root
    sys.path.insert(0, str(REPO))

# the derived values each bench must reproduce (the golden metrics)
GOLDEN_KEYS = {
    "fig7": ("fullflex1000_speedup", "partflex1000_speedup", "ordering_ok"),
    "fig13": ("fullflex1111_geomean_future", "beats_inflex_everywhere",
              "fullflex1111_hf"),
    "flexion": ("campaign_matches_serial", "all_in_unit_interval",
                "partflex1000_hf_T", "fullflex1111_hf"),
}
BENCH_MODULES = {"fig7": "benchmarks.fig7_tile",
                 "fig13": "benchmarks.fig13_futureproof",
                 "flexion": "benchmarks.flexion_bench"}
PATHS = ("reference", "campaign")
ANCHOR_RTOL = 1e-6

# filled as the parametrized runs execute: (bench, path) -> golden values
_RESULTS = {}


@pytest.fixture(scope="module")
def golden():
    with open(REPO / "BENCH_mapper.json") as f:
        doc = json.load(f)
    assert doc["bench_mode"] == "fast", \
        "committed BENCH artifact must be the fast-mode anchor"
    return doc


def _committed_values(doc, bench):
    """The pinned derived values; every engine recorded in the artifact must
    already agree on them (the artifact itself is parity-gated)."""
    per_engine = [eng[bench]["derived"] for eng in doc["engines"].values()
                  if bench in eng]
    assert per_engine, f"{bench} missing from BENCH_mapper.json"
    for other in per_engine[1:]:
        for k in GOLDEN_KEYS[bench]:
            assert other[k] == per_engine[0][k], \
                f"committed artifact disagrees with itself on {bench}:{k}"
    return {k: per_engine[0][k] for k in GOLDEN_KEYS[bench]}


def _run_bench(bench, path, monkeypatch):
    monkeypatch.setenv("REPRO_BENCH_MODE", "fast")
    if path == "reference":
        from repro.core import mapper
        from _reference_ga import run_rows
        monkeypatch.setattr(mapper, "run_batched_ga", run_rows)
    mod = importlib.import_module(BENCH_MODULES[bench])
    return mod.run(print_fn=lambda *a, **k: None)


@pytest.mark.slow
@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("bench", sorted(GOLDEN_KEYS))
def test_path_reproduces_committed_metrics(bench, path, golden, monkeypatch):
    derived = _run_bench(bench, path, monkeypatch)
    got = {k: derived[k] for k in GOLDEN_KEYS[bench]}
    _RESULTS[(bench, path)] = got
    for key, want in _committed_values(golden, bench).items():
        have = got[key]
        if isinstance(want, float):
            assert have == pytest.approx(want, rel=ANCHOR_RTOL), (
                f"{bench}.{key} via the {path} path drifted from the "
                f"committed golden value: {have!r} != {want!r} — if the "
                f"change is intentional, regenerate BENCH_mapper.json "
                f"(see docs/mapper.md)")
        else:
            assert have == want, (
                f"{bench}.{key} via the {path} path flipped from the "
                f"committed golden value {want!r} to {have!r}")


@pytest.mark.slow
@pytest.mark.parametrize("bench", sorted(GOLDEN_KEYS))
def test_paths_agree_bit_identically(bench):
    """The reference GA and the engine must agree exactly — same machine,
    same process, so this is the unforgiving form of the parity
    contract."""
    runs = {p: _RESULTS.get((bench, p)) for p in PATHS}
    if any(v is None for v in runs.values()):
        pytest.skip("per-path runs were deselected")
    ref = runs[PATHS[0]]
    for path in PATHS[1:]:
        assert runs[path] == ref, (
            f"{bench}: {path} path disagrees with {PATHS[0]}: "
            f"{runs[path]} != {ref}")
