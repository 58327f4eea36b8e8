"""``correct`` of the ``kimi-k2.decode32k`` cell at a size a CPU test run
holds (the 5 layers kept, GA 32 x 20, 5 classes): a sound run passes the
cell's own limits, and the control (the reference in bfloat16 in the
program's place) fails them.  So does each fault of the layer kinds'
mathematics, planted here under the timed path: (a) a grouped layer's
weights reused across its groups (the plain weight mask), (b) a ragged
layer costed as G groups at the mean load, (c) a ragged layer with every
group padded to the largest load.
"""
import contextlib
import time

import pytest

from lib.harness import BENCH, Ctx, load_cell, load_json, run_cell
from lib.spans import Spans

CELL = "kimi-k2.decode32k"
SEED = 2**31 + 15
SECONDS = 0.01      # one study a window, whatever the CPU's speed


@pytest.fixture(scope="module")
def small():
    """``(config, limits, ctx, state, window, generator)`` of a sound run."""
    _, cell, config, mix, limits, gen = load_cell(CELL)
    config = dict(config, classes=["1000", "1100", "0011", "11111", "00001"],
                  flexion_samples=2000,
                  ga=dict(config["ga"], population=32, generations=20))
    ctx = Ctx(cell=cell, config=config, mix=mix, seed=SEED, seconds=SECONDS,
              chips=1, spans=Spans(False))
    state = gen.prepare(ctx)
    return config, limits, ctx, state, gen.window(ctx, state), gen


def _run(config):
    return run_cell(CELL, SEED, SECONDS, False, time.perf_counter(),
                    require_device=False, config=config)


def _clear_programs():
    from repro.core import cost_model, engine, mapper
    for fn in (engine._ga_program, engine._ga_program_ragged,
               cost_model.evaluate_rows, cost_model.evaluate_population,
               cost_model.evaluate_mapping, mapper._fixed_configs_objective):
        fn.clear_cache()


@contextlib.contextmanager
def _grouped_as_plain(monkeypatch):
    from repro.core import cost_model
    real = cost_model.evaluate_mapping_impl

    def plain_mask(*args, **kwargs):
        if len(args) > 10:
            args = args[:10] + (None,)
        kwargs.pop("grouped", None)
        return real(*args, **kwargs)

    monkeypatch.setattr(cost_model, "evaluate_mapping_impl", plain_mask)
    _clear_programs()
    try:
        yield
    finally:
        monkeypatch.setattr(cost_model, "evaluate_mapping_impl", real)
        _clear_programs()


def _ragged_rows(monkeypatch, rows_of):
    """Every ragged layer costed as its G groups at ``rows_of(rows)``."""
    from repro.core.workloads import Layer
    real = Layer.group_dims

    def group_dims(self):
        if not self.ragged:
            return real(self)
        k, c, _, _, r, s = self.dims
        n = rows_of(self.group_rows)
        return tuple((k, c, n, 1, r, s) for _ in self.group_rows)

    monkeypatch.setattr(Layer, "group_dims", group_dims)
    return contextlib.nullcontext()


FAULTS = {
    "grouped_weights_shared": _grouped_as_plain,
    "ragged_mean_load": lambda mp: _ragged_rows(
        mp, lambda rows: round(sum(rows) / len(rows))),
    "ragged_padded_to_largest": lambda mp: _ragged_rows(mp, max),
}


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


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_fails(small, fault, monkeypatch):
    with FAULTS[fault](monkeypatch):
        out = _run(small[0])
    assert not out["correct"]
    assert out["checks"]["cost_gap"]["value"] > \
        out["checks"]["cost_gap"]["limit"], out["checks"]
