"""The ``stream_share.campaign`` reader on made-up study timings: the
share of live engine rows that drew a stream of their own, summed over
the window's studies, and nothing from a program without the counter."""
import importlib.util
from pathlib import Path

import pytest

READER = (Path(__file__).resolve().parents[1] / "metrics"
          / "stream_share.campaign.py")


@pytest.fixture(scope="module")
def read():
    spec = importlib.util.spec_from_file_location("stream_share", READER)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _view(*studies):
    return {"counters": {"timings": list(studies)}}


def test_share_sums_over_the_studies(read):
    a = {"engine.prepare:rows": 3648, "engine.prepare.draws:streams": 100}
    b = {"engine.prepare:rows": 3648, "engine.prepare.draws:streams": 98}
    assert read(_view(a)) == pytest.approx(100 * 100 / 3648)
    assert read(_view(a, b)) == pytest.approx(100 * 198 / 7296)


def test_nothing_without_the_counter_or_rows(read):
    assert read(_view()) is None
    assert read({"counters": {}}) is None
    # a program that does not count streams reads nothing
    assert read(_view({"engine.prepare:rows": 3648})) is None
    assert read(_view({"engine.prepare:rows": 3648,
                       "engine.prepare.draws:streams": 100},
                      {"engine.prepare:rows": 3648})) is None
    assert read(_view({"engine.prepare.draws:streams": 0})) is None
