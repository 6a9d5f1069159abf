"""The bin finder's array paths against the walks they replaced
(PR 31: ``greedy_find_bin``'s running-sum path for a column in which no
value fills a bin alone, ``_count_in_bins``, ``_distinct_values``).
The walks below are the parent's code, kept here as the oracle: same
boundaries, same counts, same mapper, value for value."""

import math

import numpy as np
import pytest

from lightgbm_tpu.data import binning
from lightgbm_tpu.data.binning import (BinMapper, _count_in_bins,
                                       _distinct_values, _next_after_up,
                                       _double_equal_ordered,
                                       greedy_find_bin)


def _old_greedy_find_bin(distinct_values, counts, max_bin, total_cnt,
                         min_data_in_bin):
    num_distinct = len(distinct_values)
    bounds = []
    if num_distinct <= max_bin:
        cur = 0
        for i in range(num_distinct - 1):
            cur += int(counts[i])
            if cur >= min_data_in_bin:
                val = _next_after_up(
                    (float(distinct_values[i])
                     + float(distinct_values[i + 1])) / 2.0)
                if not bounds or not _double_equal_ordered(bounds[-1], val):
                    bounds.append(val)
                    cur = 0
        bounds.append(math.inf)
        return bounds
    if min_data_in_bin > 0:
        max_bin = max(1, min(max_bin, total_cnt // min_data_in_bin))
    mean_bin_size = total_cnt / max_bin
    rest_bin_cnt = max_bin
    rest_sample_cnt = total_cnt
    is_big = counts >= mean_bin_size
    rest_bin_cnt -= int(is_big.sum())
    rest_sample_cnt -= int(counts[is_big].sum())
    mean_bin_size = rest_sample_cnt / max(rest_bin_cnt, 1)
    upper_bounds = [math.inf] * max_bin
    lower_bounds = [math.inf] * max_bin
    bin_cnt = 0
    lower_bounds[0] = float(distinct_values[0])
    cur = 0
    for i in range(num_distinct - 1):
        if not is_big[i]:
            rest_sample_cnt -= int(counts[i])
        cur += int(counts[i])
        if (is_big[i] or cur >= mean_bin_size
                or (is_big[i + 1]
                    and cur >= max(1.0, mean_bin_size * 0.5))):
            upper_bounds[bin_cnt] = float(distinct_values[i])
            bin_cnt += 1
            lower_bounds[bin_cnt] = float(distinct_values[i + 1])
            if bin_cnt >= max_bin - 1:
                break
            cur = 0
            if not is_big[i]:
                rest_bin_cnt -= 1
                mean_bin_size = rest_sample_cnt / max(rest_bin_cnt, 1)
    bin_cnt += 1
    for i in range(bin_cnt - 1):
        val = _next_after_up((upper_bounds[i] + lower_bounds[i + 1]) / 2.0)
        if not bounds or not _double_equal_ordered(bounds[-1], val):
            bounds.append(val)
    bounds.append(math.inf)
    return bounds


def _old_count_in_bins(dv, cn, upper_bounds):
    cnt_in_bin = [0] * len(upper_bounds)
    i_bin = 0
    for i in range(len(dv)):
        if dv[i] > upper_bounds[i_bin]:
            i_bin += 1
        cnt_in_bin[i_bin] += int(cn[i])
    return cnt_in_bin


def _old_distinct_values(values, zero_cnt):
    values = np.sort(values, kind="stable")
    num_sample_values = len(values)
    if num_sample_values == 0:
        return np.asarray([0.0]), np.asarray([zero_cnt])
    new_grp = np.concatenate(
        [[True], values[1:] > np.nextafter(values[:-1], np.inf)])
    starts = np.nonzero(new_grp)[0]
    ends = np.concatenate([starts[1:], [num_sample_values]])
    dvals = values[ends - 1]
    distinct_values = dvals.tolist()
    counts = (ends - starts).astype(np.int64).tolist()
    if zero_cnt > 0:
        if distinct_values[0] > 0.0:
            distinct_values.insert(0, 0.0)
            counts.insert(0, zero_cnt)
        elif distinct_values[-1] < 0.0:
            distinct_values.append(0.0)
            counts.append(zero_cnt)
        else:
            pos = int(np.searchsorted(dvals, 0.0))
            if 0 < pos < len(distinct_values) \
                    and distinct_values[pos - 1] < 0.0 \
                    and distinct_values[pos] > 0.0:
                distinct_values.insert(pos, 0.0)
                counts.insert(pos, zero_cnt)
    return np.asarray(distinct_values), np.asarray(counts)


def _column(kind: str, n: int = 6000) -> np.ndarray:
    rng = np.random.default_rng(len(kind) * 1009 + n)
    if kind == "continuous":        # no value fills a bin: the new path
        return rng.standard_normal(n)
    if kind == "unit-row":          # an Epsilon column: 1 / sqrt(2000)
        return (rng.standard_normal(n) / math.sqrt(2000.0)).astype(
            np.float32).astype(np.float64)
    if kind == "heavy-ties":        # a few values fill bins alone
        return np.round(rng.standard_normal(n), 1)
    if kind == "one-big":           # one value holds 40 % of the rows
        x = rng.standard_normal(n)
        x[rng.random(n) < 0.4] = 0.25
        return x
    if kind == "big-neighbours":    # big values next to small runs
        return np.concatenate([np.repeat([-1.0, 0.5, 2.0], n // 5),
                               rng.uniform(-2, 3, n - 3 * (n // 5))])
    if kind == "ties-at-mean":      # every running sum lands ON the mean
        return np.repeat(np.arange(n // 10, dtype=np.float64), 10)
    if kind == "ties-uneven":       # counts 1..7: sums pass and hit it
        return np.repeat(np.arange(1500, dtype=np.float64),
                         rng.integers(1, 8, 1500))
    if kind == "few-distinct":      # at most max_bin values: first branch
        return rng.integers(0, 40, n).astype(np.float64)
    if kind == "positive":          # an implicit zero goes in front
        return rng.gamma(2.0, size=n)
    if kind == "negative":          # ... or behind
        return -rng.gamma(2.0, size=n)
    raise KeyError(kind)


COLUMNS = ["continuous", "unit-row", "heavy-ties", "one-big",
           "big-neighbours", "ties-at-mean", "ties-uneven", "few-distinct"]


@pytest.mark.parametrize("min_data_in_bin", [1, 3, 40])
@pytest.mark.parametrize("max_bin", [15, 63, 255])
@pytest.mark.parametrize("kind", COLUMNS)
def test_greedy_find_bin_gives_the_walks_boundaries(kind, max_bin,
                                                    min_data_in_bin):
    dv, cn = _distinct_values(_column(kind), 0)
    total = int(cn.sum())
    new = greedy_find_bin(dv, cn, max_bin, total, min_data_in_bin)
    old = _old_greedy_find_bin(dv, cn, max_bin, total, min_data_in_bin)
    assert new == old
    # which path ran: the running sums where no value is big
    if kind in ("continuous", "unit-row"):
        assert len(dv) > max_bin and not (cn >= total / max_bin).any()


@pytest.mark.parametrize("kind", COLUMNS + ["positive", "negative"])
@pytest.mark.parametrize("zero_cnt", [0, 1, 700])
def test_distinct_values_and_counts(kind, zero_cnt):
    x = _column(kind, 3000)
    # values one ulp apart are one value
    x = np.concatenate([x, np.nextafter(x[:50], np.inf)])
    dv, cn = _distinct_values(x, zero_cnt)
    dv_old, cn_old = _old_distinct_values(x, zero_cnt)
    assert dv.tolist() == dv_old.tolist() and cn.tolist() == cn_old.tolist()
    ub = greedy_find_bin(dv, cn, 63, int(cn.sum()), 3)
    assert _count_in_bins(dv, cn, ub) == _old_count_in_bins(dv, cn, ub)


def test_count_in_bins_lags_past_an_empty_bin():
    """The walk moves on by one bin a value: past a bin no value falls
    into (the zero bin of a column without zeros) its counts lag."""
    dv = np.asarray([-2.0, -1.0, 1.0, 2.0, 3.0])
    cn = np.asarray([5, 4, 3, 2, 1])
    ub = [-1.5, -1e-35, 1e-35, 1.5, math.inf]     # bin 2 stays empty
    # by the bounds alone the rows fall 5, 4, 0, 3, 3
    assert _count_in_bins(dv, cn, ub) == _old_count_in_bins(dv, cn, ub) \
        == [5, 4, 3, 2, 1]
    assert _count_in_bins(np.zeros(1), np.asarray([7]), [math.inf]) == [7]


MAPPERS = [
    # (column, rows the sample stands for beyond its values, NaN share,
    #  find_bin options)
    ("continuous", 0, 0.0, {}),
    ("continuous", 0, 0.1, {}),                       # the NaN bin
    ("continuous", 0, 0.1, {"use_missing": False}),
    ("continuous", 2000, 0.0, {}),                    # the zero bin
    ("continuous", 2000, 0.05, {"zero_as_missing": True}),
    ("unit-row", 0, 0.0, {}),
    ("heavy-ties", 500, 0.02, {}),
    ("one-big", 0, 0.0, {}),
    ("big-neighbours", 100, 0.0, {}),
    ("ties-at-mean", 0, 0.0, {}),
    ("ties-uneven", 1, 0.0, {}),
    ("few-distinct", 300, 0.0, {}),
    ("positive", 1500, 0.0, {}),
    ("negative", 1500, 0.03, {}),
]


@pytest.mark.parametrize("max_bin", [16, 255])
@pytest.mark.parametrize("kind,implicit_zeros,nan_share,options", MAPPERS)
def test_find_bin_gives_the_same_mapper(monkeypatch, kind, implicit_zeros,
                                        nan_share, options, max_bin):
    x = _column(kind, 4000)
    if nan_share:
        x[np.random.default_rng(5).random(len(x)) < nan_share] = np.nan

    def mapper():
        m = BinMapper()
        m.find_bin(x, len(x) + implicit_zeros, max_bin, 3, 20, True,
                   **options)
        return m
    new = mapper()
    monkeypatch.setattr(binning, "greedy_find_bin", _old_greedy_find_bin)
    monkeypatch.setattr(binning, "_count_in_bins", _old_count_in_bins)
    monkeypatch.setattr(binning, "_distinct_values", _old_distinct_values)
    old = mapper()
    assert new.num_bin == old.num_bin and new.num_bin > 1
    assert new.missing_type == old.missing_type
    np.testing.assert_array_equal(new.bin_upper_bound, old.bin_upper_bound)
    assert (new.min_val, new.max_val, new.default_bin, new.most_freq_bin,
            new.is_trivial, new.sparse_rate) \
        == (old.min_val, old.max_val, old.default_bin, old.most_freq_bin,
            old.is_trivial, old.sparse_rate)
    probe = np.concatenate([x[:500], [0.0, np.nan, -1e9, 1e9]])
    np.testing.assert_array_equal(new.values_to_bins(probe),
                                  old.values_to_bins(probe))
