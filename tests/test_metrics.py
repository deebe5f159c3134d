"""Zero-completion safety of the metric reductions.

Short-horizon quick runs (and padded cells with tiny traced horizons) can
legitimately finish with *no completed jobs*; every percentile / mean
reduction must then produce defined zeros, never NaN -- a NaN row is a CI
trajectory-diff regression by design (``benchmarks/diff.py``).
"""
import warnings

import numpy as np

from repro.core.care import metrics, slotted_sim


EMPTY = np.array([], dtype=np.int64)


def test_jct_summary_empty_is_zero_not_nan():
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # np raises RuntimeWarning on empty mean
        s = metrics.jct_summary(EMPTY)
    assert s == {"count": 0, "mean": 0.0, "p50": 0.0, "p90": 0.0,
                 "p99": 0.0, "p999": 0.0}
    assert all(np.isfinite(v) for v in s.values())


def test_jct_summary_accepts_lists():
    s = metrics.jct_summary(np.asarray([4, 4, 4]))
    assert s["count"] == 3 and s["mean"] == 4.0 and s["p999"] == 4.0


def test_mean_jct_empty_and_nonempty():
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert metrics.mean_jct(EMPTY) == 0.0
    assert metrics.mean_jct(np.asarray([2, 4])) == 3.0


def test_ccdf_empty_samples():
    grid, frac = metrics.ccdf(EMPTY)
    assert np.all(frac == 0.0)
    assert np.all(np.isfinite(frac))


def test_ccdf_dominates_empty_inputs():
    assert metrics.ccdf_dominates(EMPTY, EMPTY) in (True, False)


def test_relative_communication_zero_departures():
    r = slotted_sim.SimResult(
        jct=EMPTY, arrivals=0, departures=0, messages=0, max_aq=0,
        max_queue=0, overflow=False,
        per_server_arrivals=np.zeros(4, np.int64),
        final_q=np.zeros(4, np.int64),
    )
    assert metrics.relative_communication(r, "jsaq") == 0.0
    assert np.isfinite(metrics.relative_communication(r, "jsq"))


def test_simulation_with_zero_completions_yields_finite_summary():
    # A horizon shorter than one (deterministic) service: jobs arrive,
    # none can finish, whatever the random stream.
    cfg = slotted_sim.SimConfig(
        slots=5, load=1.0, mean_service=50, service="deterministic"
    )
    res = slotted_sim.simulate(__import__("jax").random.key(0), cfg)
    s = metrics.jct_summary(res.jct)
    assert res.jct.size == 0
    assert all(np.isfinite(v) for v in s.values())


# --- log-bucket JCT histogram (streaming-engine tail accumulator) ---------


def test_jct_bucket_edges_partition_int32():
    """Every bucket's edge range maps back to that bucket, exhaustively
    near every boundary (and the bucket index is monotone in j)."""
    edges = metrics.jct_bucket_edges()
    assert edges.shape == (metrics.HIST_BUCKETS + 1,)
    assert edges[0] == 1 and edges[-1] == 2**31
    assert np.all(np.diff(edges) > 0)
    # Probe each boundary from both sides plus the bucket interior.
    for b in range(metrics.HIST_BUCKETS):
        lo, hi = int(edges[b]), int(edges[b + 1])
        probes = [lo, lo + (hi - lo) // 2, hi - 1]
        got = metrics.jct_bucket(np.asarray(probes, np.int64))
        assert np.all(got == b), (b, probes, got)


def test_jct_bucket_matches_between_numpy_and_jax():
    import jax.numpy as jnp

    j = np.concatenate([
        np.arange(1, 70),
        2 ** np.arange(2, 31, dtype=np.int64),
        2 ** np.arange(2, 31, dtype=np.int64) - 1,
        np.asarray([np.iinfo(np.int32).max]),
    ])
    b_np = metrics.jct_bucket(j, xp=np)
    b_jx = np.asarray(metrics.jct_bucket(jnp.asarray(j), xp=jnp))
    assert np.array_equal(b_np, b_jx)


def test_jct_bucket_clips_nonpositive():
    assert metrics.jct_bucket(np.asarray([0, -5, 1])).tolist() == [0, 0, 0]


def test_log_hist_quantiles_empty_is_zero():
    hist = np.zeros(metrics.HIST_BUCKETS, np.int64)
    q = metrics.log_hist_quantiles(hist, (0.5, 0.99))
    assert np.all(q == 0.0) and np.all(np.isfinite(q))


def test_log_hist_quantiles_exact_small_buckets():
    # Samples 1/2/3 live in single-value buckets: quantiles are exact.
    samples = np.asarray([1] * 10 + [2] * 10 + [3] * 10)
    hist = np.bincount(metrics.jct_bucket(samples),
                       minlength=metrics.HIST_BUCKETS)
    p50, = metrics.log_hist_quantiles(hist, (0.5,))
    assert p50 == 2.0


def test_log_hist_quantiles_bounded_by_sub_octave():
    rng = np.random.default_rng(0)
    samples = rng.integers(1, 10_000, size=20_000)
    hist = np.bincount(metrics.jct_bucket(samples),
                       minlength=metrics.HIST_BUCKETS)
    for q in (0.5, 0.9, 0.99, 0.999):
        est, = metrics.log_hist_quantiles(hist, (q,))
        exact = np.quantile(samples, q)
        # A bucket spans <= 25% relative width, so the histogram estimate
        # lands within one sub-octave of the exact sample quantile.
        assert abs(est - exact) <= 0.25 * exact + 1.0, (q, est, exact)


def test_stream_summary_empty_and_roundtrip():
    empty = metrics.stream_summary(
        0, 0.0, 0.0, 0, np.zeros(metrics.HIST_BUCKETS, np.int64)
    )
    assert empty["count"] == 0 and empty["p999"] == 0.0
    assert all(np.isfinite(v) for v in empty.values())

    samples = np.asarray([10, 20, 30, 40], np.int64)
    hist = np.bincount(metrics.jct_bucket(samples),
                       minlength=metrics.HIST_BUCKETS)
    s = metrics.stream_summary(
        samples.size, samples.mean(),
        ((samples - samples.mean()) ** 2).sum(), samples.max(), hist,
    )
    assert s["count"] == 4 and s["mean"] == 25.0 and s["max"] == 40
    assert abs(s["std"] - samples.std()) < 1e-6


def test_stream_summary_all_discarded_takes_zero_count_path():
    # Warmup can discard every completion from the quantile histogram
    # while the exact max was tracked pre-discard: the summary must take
    # the zero-count disambiguated path (count=0, zero quantiles, max
    # preserved), never clamp the empty histogram's zero "quantiles"
    # into [0, max] as if they described a sample.
    empty_hist = np.zeros(metrics.HIST_BUCKETS, np.int64)
    s = metrics.stream_summary(0, 0.0, 0.0, 37, empty_hist)
    assert s["count"] == 0 and s["max"] == 37
    assert s["p50"] == s["p90"] == s["p99"] == s["p999"] == 0.0
    assert all(np.isfinite(v) for v in s.values())
    # Moments tracked but no histogram mass (every sample dropped from
    # the quantile buckets): same disambiguated path, not a 0.0
    # "quantile" next to a nonzero count.
    s = metrics.stream_summary(12, 37.0, 4.0, 37, empty_hist)
    assert s["count"] == 0 and s["max"] == 37
    assert s["p999"] == 0.0


def test_stream_summary_single_bucket_clamps_to_max():
    # Every sample in one bucket: interpolation inside the bucket would
    # overshoot the sample maximum, so the clamp must pin every quantile
    # at (or below) the tracked exact max -- never above it.
    samples = np.full(50, 17, np.int64)
    hist = np.bincount(metrics.jct_bucket(samples),
                       minlength=metrics.HIST_BUCKETS)
    s = metrics.stream_summary(
        samples.size, 17.0, 0.0, 17, hist,
    )
    for k in ("p50", "p90", "p99", "p999"):
        assert 16.0 <= s[k] <= 17.0, (k, s[k])
    assert s["max"] == 17 and s["std"] == 0.0


def test_stream_summary_single_sample_is_finite():
    hist = np.bincount(metrics.jct_bucket(np.asarray([5])),
                       minlength=metrics.HIST_BUCKETS)
    s = metrics.stream_summary(1, 5.0, 0.0, 5, hist)
    assert s["count"] == 1
    assert all(np.isfinite(v) for v in s.values())
    assert s["p999"] <= 5.0


def test_token_summary_empty_window_is_finite_zeros():
    # The pull-token counters' analogue of the jct_summary contract: an
    # empty window (no slots run, no jobs routed) yields finite zeros
    # with a count field, so aggregation never divides by zero.
    s = metrics.token_summary(0, 0, 0, 0)
    assert s == {"count": 0, "mean_tokens": 0.0, "miss_rate": 0.0,
                 "hit_rate": 0.0}
    assert all(np.isfinite(v) for v in s.values())


def test_token_summary_partial_windows():
    # Slots ran but nothing was routed (zero-arrival window): pool
    # occupancy is defined, the rate fields stay finite zeros.
    s = metrics.token_summary(30, 0, 10, 0)
    assert s["count"] == 0 and s["mean_tokens"] == 3.0
    assert s["miss_rate"] == 0.0 and s["hit_rate"] == 0.0
    # Routed jobs but a zero-slot window (degenerate caller) stays finite.
    s = metrics.token_summary(0, 2, 0, 8)
    assert s["count"] == 8 and s["mean_tokens"] == 0.0
    assert s["miss_rate"] == 0.25 and s["hit_rate"] == 0.75


def test_token_summary_rates():
    s = metrics.token_summary(120, 25, 60, 100)
    assert s["count"] == 100
    assert s["mean_tokens"] == 2.0
    assert s["miss_rate"] == 0.25 and s["hit_rate"] == 0.75
