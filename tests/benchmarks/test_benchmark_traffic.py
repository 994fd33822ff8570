"""The traffic generator: same seed, same stream; another seed, the same
amount of work from other tokens."""

import numpy as np
import pytest

from benchmarks import common
from benchmarks.generators import uniform_rows

BIG = 2**31 + 12345     # the driver's seeds pass 32 signed bits


def test_uniform_rows_repeat_for_a_seed_and_differ_across_seeds():
    t = {"token_rows": 32}
    a, b = uniform_rows.make(t, 17, 257, BIG), uniform_rows.make(t, 17, 257, BIG)
    c = uniform_rows.make(t, 17, 257, BIG + 1)
    assert a.shape == (32, 17) and a.dtype == np.int32
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < 257


@pytest.mark.parametrize("seed", [0, 7, BIG, 2**32 + 5])
def test_every_seed_gives_the_same_amount_of_work(seed):
    a = uniform_rows.make({"token_rows": 8}, 33, 1000, seed)
    assert a.shape == (8, 33)


def test_sub_seeds_fit_31_bits_and_differ_by_seed():
    a, b = common.sub_seeds(BIG), common.sub_seeds(BIG + 1)
    assert a == common.sub_seeds(BIG) and a != b
    assert len(set(a)) == 4 and all(0 <= s < 2**31 for s in a + b)
