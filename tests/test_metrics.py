"""LatencyHistogram: fixed log-spaced buckets, derivable percentiles,
mergeable snapshots, thread safety. Fast tier."""
import threading

from repro.core.metrics import BUCKET_BOUNDS_MS, LatencyHistogram


def test_bucket_layout_is_fixed_and_log_spaced():
    assert len(BUCKET_BOUNDS_MS) == 24
    assert BUCKET_BOUNDS_MS[0] == 0.01
    for lo, hi in zip(BUCKET_BOUNDS_MS, BUCKET_BOUNDS_MS[1:]):
        assert hi == lo * 2                       # exact x2 spacing
    # the layout covers the serving range: 10us .. ~84s
    assert BUCKET_BOUNDS_MS[-1] > 60_000


def test_observe_lands_in_the_right_bucket():
    h = LatencyHistogram()
    h.observe(0.001)                              # 1 ms
    snap = h.snapshot()
    assert snap["count"] == 1 and sum(snap["bucket_counts"]) == 1
    # 1 ms falls in the (0.64, 1.28] bucket
    i = snap["bucket_counts"].index(1)
    assert snap["bucket_le_ms"][i] == 1.28
    # overflow goes to the +Inf bucket, not out of range
    h.observe(1000.0)                             # 1000 s
    snap = h.snapshot()
    assert snap["bucket_counts"][-1] == 1
    assert snap["bucket_le_ms"][-1] == "inf"
    assert snap["min_ms"] == 1.0 and snap["max_ms"] == 1e6


def test_percentiles_derivable_from_any_snapshot():
    h = LatencyHistogram()
    assert h.percentile(50) is None               # empty: no answer
    for _ in range(100):
        h.observe(0.001)                          # all in (0.64, 1.28]
    snap = h.snapshot()
    assert 0.64 <= snap["p50_ms"] <= 1.28
    assert 0.64 <= snap["p99_ms"] <= 1.28
    # bimodal: 90 fast (1ms) + 10 slow (100ms) -> p50 fast, p99 slow
    h2 = LatencyHistogram()
    for _ in range(90):
        h2.observe(0.001)
    for _ in range(10):
        h2.observe(0.1)
    assert h2.percentile(50) <= 1.28
    assert h2.percentile(99) > 50.0
    # snapshots merge by adding counts — p99 derivable from the merge
    merged = [a + b for a, b in zip(h.snapshot()["bucket_counts"],
                                    h2.snapshot()["bucket_counts"])]
    p99 = LatencyHistogram.percentile_from(merged, 99)
    assert p99 > 50.0


def test_negative_and_zero_observations_clamp_to_first_bucket():
    h = LatencyHistogram()
    h.observe(0.0)
    h.observe(-1.0)                               # clock skew guard
    snap = h.snapshot()
    assert snap["bucket_counts"][0] == 2 and snap["min_ms"] == 0.0


def test_concurrent_observe_loses_nothing():
    h = LatencyHistogram()
    n_threads, per = 8, 500

    def worker(i):
        for j in range(per):
            h.observe((i + j % 7) * 1e-4)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = h.snapshot()
    assert snap["count"] == n_threads * per
    assert sum(snap["bucket_counts"]) == n_threads * per


def test_bucket_lookup_matches_a_linear_scan_at_every_bound():
    """``observe`` bisects the bounds; each value lands where a scan for
    the first bound >= the value puts it, exact bounds included."""
    def scan(ms):
        for i, bound in enumerate(BUCKET_BOUNDS_MS):
            if ms <= bound:
                return i
        return len(BUCKET_BOUNDS_MS)

    values = [0.0, 1e-9, 1e9]
    for b in BUCKET_BOUNDS_MS:
        values += [b, b * (1 - 1e-12), b * (1 + 1e-12), b * 1.5]
    for ms in values:
        h = LatencyHistogram()
        h.observe(ms / 1e3)
        counts = h.snapshot()["bucket_counts"]
        assert counts.index(1) == scan(max(ms / 1e3, 0.0) * 1e3), ms
