"""The study generator: the same seed gives the same studies, another seed
other ones, and every GA seed is a whole number that a GA config takes."""
import itertools

import pytest

from generators.studies import study_seeds


@pytest.mark.parametrize("seed", [0, 2**31 + 17, 2**40 + 3])
def test_study_seeds_repeat_per_seed(seed):
    a = list(itertools.islice(study_seeds(seed), 5))
    assert a == list(itertools.islice(study_seeds(seed), 5))
    assert a != list(itertools.islice(study_seeds(seed + 1), 5))
    assert all(0 <= s < 2**31 - 1 for s in a)
