"""Numeric contracts of the summary statistics every report is built from
(``utils.metrics.summarize``, ``stats1d.calculate_statistics``,
``stats3d.calculate_statistics_3d``): plain numpy, one implementation."""

import numpy as np

from dlbb_tpu.stats.stats1d import calculate_statistics
from dlbb_tpu.stats.stats3d import calculate_statistics_3d
from dlbb_tpu.utils.metrics import SUMMARY_KEYS, summarize

RNG = np.random.default_rng(42)


def test_summarize_matches_numpy():
    for n in (1, 2, 7, 100, 10_001):
        xs = RNG.lognormal(size=n)
        got = summarize(xs)
        assert got["count"] == n
        np.testing.assert_allclose(got["mean"], xs.mean(), rtol=1e-12)
        np.testing.assert_allclose(got["std"], xs.std(), rtol=1e-9, atol=1e-15)
        np.testing.assert_allclose(got["min"], xs.min(), rtol=0)
        np.testing.assert_allclose(got["max"], xs.max(), rtol=0)
        np.testing.assert_allclose(got["median"], np.median(xs), rtol=1e-12)
        np.testing.assert_allclose(got["p95"], np.percentile(xs, 95),
                                   rtol=1e-12)
        np.testing.assert_allclose(got["p99"], np.percentile(xs, 99),
                                   rtol=1e-12)


def test_summarize_schema_from_a_list():
    """The schema every harness relies on, from a plain Python list."""
    out = summarize(RNG.normal(size=256).tolist())
    assert tuple(out) == SUMMARY_KEYS
    assert isinstance(out["count"], int)
    assert all(isinstance(out[k], float) for k in SUMMARY_KEYS
               if k != "count")


def test_summarize_p999_tail():
    """p99.9 (the serving-path tail metric) is numpy's linear-interpolated
    percentile, and sits between p99 and max."""
    xs = RNG.lognormal(size=5000)
    out = summarize(xs)
    np.testing.assert_allclose(out["p999"], np.percentile(xs, 99.9),
                               rtol=1e-12)
    assert out["p99"] <= out["p999"] <= out["max"]


def test_summarize_empty_series_contract():
    """An empty series returns explicit NaN-valued keys with count 0 —
    never a bare {} a downstream stats pass would KeyError on."""
    out = summarize([])
    assert set(out) == set(SUMMARY_KEYS)
    assert out["count"] == 0
    assert all(np.isnan(v) for k, v in out.items() if k != "count")


def test_load_imbalance_matches_reference_formula():
    """(max - mean) / mean * 100 over the per-rank means; 0 when the mean
    of means is not positive (never a division by zero)."""
    means = RNG.uniform(1.0, 2.0, size=16)
    expected = (means.max() - means.mean()) / means.mean() * 100.0
    stats = calculate_statistics(means[:, None].tolist())
    np.testing.assert_allclose(stats["load_imbalance_percent"], expected,
                               rtol=1e-12)
    assert calculate_statistics(
        np.zeros((4, 3)).tolist())["load_imbalance_percent"] == 0.0


def test_row_means_match_numpy():
    mat = RNG.uniform(1e-5, 1e-3, size=(8, 100))
    stats = calculate_statistics(mat.tolist())
    np.testing.assert_allclose(stats["per_rank_means_us"],
                               mat.mean(axis=1) * 1e6, rtol=1e-12)


def test_stats1d_pipeline_numbers():
    timings = RNG.lognormal(mean=-8, size=(4, 50))
    stats = calculate_statistics(timings.tolist())
    flat = timings.ravel()
    np.testing.assert_allclose(stats["mean_time_us"], flat.mean() * 1e6,
                               rtol=1e-9)
    np.testing.assert_allclose(stats["p99_time_us"],
                               np.percentile(flat, 99) * 1e6, rtol=1e-9)
    means = timings.mean(axis=1)
    expected_li = (means.max() - means.mean()) / means.mean() * 100.0
    np.testing.assert_allclose(stats["load_imbalance_percent"], expected_li,
                               rtol=1e-9)


def test_stats3d_matches_numpy():
    """calculate_statistics_3d maps summarize's seconds-scale fields onto
    the reference's ms keys, field for field."""
    timings = RNG.uniform(1e-4, 5e-3, size=(4, 25)).tolist()
    flat = np.asarray(timings).ravel()
    want = {
        "mean_time_ms": float(flat.mean() * 1e3),
        "median_time_ms": float(np.median(flat) * 1e3),
        "min_time_ms": float(flat.min() * 1e3),
        "max_time_ms": float(flat.max() * 1e3),
    }
    got = calculate_statistics_3d(timings)
    assert set(got) == set(want)
    for k, v in want.items():
        np.testing.assert_allclose(got[k], v, rtol=1e-12, atol=0)
