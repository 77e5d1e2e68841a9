"""Roofline counts against hand-computed cases, and the peaks table."""
import pytest

from bench import roofline


def test_rng_round_counts():
    # 8 vertices, 48 slots, 48 pairs, D=128: 384 rows, fewer than n
    flops, nbytes = roofline.rng_round(8, 1000, 128, 48, 48)
    assert flops == 3 * 128 * 8 * 48
    assert nbytes == (384 * 128 * 4 + 8 * 48 * 8 + 8 * 48 * 8
                      + 8 * 48 * 12 + 8 * 48)


def test_rng_round_reads_no_more_rows_than_the_corpus_holds():
    _, nbytes = roofline.rng_round(100, 1000, 128, 48, 48)
    assert nbytes == (1000 * 128 * 4 + 100 * 48 * 8 + 100 * 48 * 8
                      + 100 * 48 * 12 + 100 * 48)


def test_search_expand_counts():
    flops, nbytes = roofline.search_expand(1000, 10, 960)
    assert flops == 3 * 960 * 1000
    assert nbytes == 1010 * 960 * 4 + 1000 * 9


def test_least_seconds_takes_the_larger_bound():
    peak = roofline.peaks("TPU v5 lite")
    assert peak == {"flops": 197e12, "bytes": 819e9}
    assert roofline.least_seconds(197e12, 1.0, peak) == pytest.approx(1.0)
    assert roofline.least_seconds(1.0, 819e9, peak) == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
