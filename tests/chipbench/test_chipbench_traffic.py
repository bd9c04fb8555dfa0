"""The seeded generator: the same seed gives the same rows, any whole
number is a seed, and the rows follow the chain."""
import numpy as np
import pytest

from chipbench.traffic_gen import MarkovSource, seeded_rng

BIG = 2 ** 31 + 12345


@pytest.mark.parametrize("seed", [0, BIG])
def test_markov_rows_depend_only_on_seed_and_index(seed):
    src = MarkovSource(seed, 512, 8, 1.2)
    whole = src.sample(10, 6, 32)
    part = src.sample(13, 2, 32)
    assert np.array_equal(whole["tokens"][3:5], part["tokens"])
    again = MarkovSource(seed, 512, 8, 1.2).sample(10, 6, 32)
    assert np.array_equal(whole["tokens"], again["tokens"])
    assert np.array_equal(whole["tokens"][:, 1:], whole["labels"][:, :-1])
    assert whole["tokens"].dtype == np.int32
    assert whole["tokens"].max() < 512


def test_markov_rows_follow_the_chain():
    src = MarkovSource(4, 256, 4, 1.2)
    b = src.sample(0, 8, 64)
    for row_t, row_y in zip(b["tokens"], b["labels"]):
        for t, y in zip(row_t, row_y):
            assert y in src.table[t]
    other = MarkovSource(5, 256, 4, 1.2).sample(0, 8, 64)
    assert not np.array_equal(b["tokens"], other["tokens"])


def test_seeds_are_any_whole_number():
    big = seeded_rng(2 ** 33 + 5, 2).random(4)
    assert np.array_equal(big, seeded_rng(2 ** 33 + 5, 2).random(4))
    assert not np.array_equal(big, seeded_rng(5, 2).random(4))
    with pytest.raises(ValueError):
        seeded_rng(-1)
