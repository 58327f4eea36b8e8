"""``correct`` at a size a CPU test run holds: a sound run passes the
cell's own limits; the control (the reference in bfloat16 in the
program's place) and each fault of ``bench/lib/faults.py``, planted under
the timed path, fail them.  The harness's look for a chip is skipped;
everything else of a run is driven as on the chip.
"""
import time

import pytest

from lib.faults import FAULTS, planted
from lib.harness import BENCH, Ctx, load_cell, load_json, run_cell
from lib.spans import Spans

CELL = "fig13.campaign"
SEED = 2**31 + 11
# one study a window, whatever the CPU's speed
SECONDS = 0.01


@pytest.fixture(scope="module")
def small():
    """A small study (3 models, 5 classes, GA 32 x 20) as
    ``(config, limits, ctx, state, window, generator)`` of a sound run."""
    _, cell, config, mix, limits, gen = load_cell(CELL)
    config = dict(config, classes=["1000", "1100", "0011", "11111", "00001"],
                  models=["alexnet", "dlrm", "ncf"], flexion_samples=2000,
                  ga=dict(config["ga"], population=32, generations=20))
    ctx = Ctx(cell=cell, config=config, mix=mix, seed=SEED, seconds=SECONDS,
              chips=1, spans=Spans(False))
    state = gen.prepare(ctx)
    return config, limits, ctx, state, gen.window(ctx, state), gen


def _run(config):
    return run_cell(CELL, SEED, SECONDS, False, time.perf_counter(),
                    require_device=False, config=config)


def test_sound_run_is_correct(small):
    out = _run(small[0])
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 1
    assert set(out["checks"]) == set(load_json(BENCH / "limits"
                                               / f"{CELL}.json"))


def test_control_fails(small):
    _, limits, ctx, state, win, gen = small
    control = gen.readings(ctx, state, win, control=True)
    assert any(v > limits[k]["limit"] for k, v in control.items()), control


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_fails(small, fault):
    with planted(fault):
        out = _run(small[0])
    assert not out["correct"]
    assert out["failed"] > 0 or any(
        c["value"] > c["limit"] for c in out["checks"].values()), out
