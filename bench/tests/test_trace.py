"""The trace reduction, on a small trace recorded on a TPU v5e by
``bench/tools/record_trace.py``: two engine chunks (population 8, 4
generations) under the benchmark's spans, with a 50 ms sleep between them
that no span covers."""
from pathlib import Path

import pytest

from lib import trace

SMALL = Path(__file__).resolve().parents[1] / "testdata" / "small.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(str(SMALL))


def test_busy_and_idle_cover_the_window(reduced):
    assert len(reduced.busy_s) == 1
    assert 0 < reduced.busy_s[0] < reduced.window_s
    idle = sum(reduced.idle_by_span.values())
    assert idle == pytest.approx(reduced.window_s - reduced.busy_s[0],
                                 rel=1e-6)


def test_program_time_by_jit_name(reduced):
    ga = reduced.program_s("_ga_program")
    assert set(reduced.module_s) == {k for k in reduced.module_s
                                     if k.startswith("jit__ga_program(")}
    # the program's runs hold every operation of this trace
    assert ga == pytest.approx(reduced.busy_s[0], rel=0.01)
    assert reduced.program_s("_ga_prog") == 0.0


def test_op_self_times_add_up_to_busy(reduced):
    assert sum(reduced.op_s.values()) == pytest.approx(reduced.busy_s[0],
                                                       rel=1e-6)
    name, sec = trace.top(reduced.op_s, 1)[0]
    assert name.startswith("jit__ga_program/%") and sec > 0
    assert len(trace.top(reduced.op_s)) == 10


def test_host_spans_and_idle_gaps(reduced):
    assert reduced.span_n == {"bench.engine.prepare": 2,
                              "bench.engine.collect": 2}
    # the uncovered sleep is idle time with no span open
    assert reduced.idle_by_span["idle"] >= 0.05


def test_union_and_attribution():
    assert trace._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3),
                                                              (5, 8)]
    spans = [(0, 100, "bench.outer"), (10, 20, "bench.inner")]
    gaps = [(12, 14), (30, 40), (150, 160)]
    got = trace._attribute(gaps, spans)
    assert got == pytest.approx({"bench.inner": 2e-9, "bench.outer": 1e-8,
                                 "idle": 1e-8})


def test_nested_ops_count_once():
    modules = [(0, 100, "jit_f(7)")]
    ops = [(0, 100, "%while.1 = loop"), (10, 30, "%fusion.2 = a"),
           (40, 50, "%fusion.2 = a")]
    got = trace._self_times(ops, modules)
    assert got == pytest.approx({"jit_f/%while.1": 70e-9,
                                 "jit_f/%fusion.2": 30e-9})
