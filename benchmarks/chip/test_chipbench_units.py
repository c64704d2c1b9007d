"""The benchmark's arithmetic on the CPU: percentiles, work functions,
the generator's determinism, discovery by name, seeds and the verdict."""
from __future__ import annotations

import json

import numpy as np
import pytest

from benchmarks.chip import checks, client, hoststats, spec, traffic, work


def test_percentiles_from_raw_samples():
    x = np.arange(1, 101, dtype=float)            # 1..100
    assert client.percentile(x, 50) == pytest.approx(50.5)
    assert client.percentile(x, 99) == pytest.approx(99.01)
    assert client.percentile([7.0], 99) == 7.0
    assert client.percentile([], 50) is None
    # a failed request is slower than every answer: the tail reads +inf
    tail = np.r_[np.ones(99), np.inf]
    assert client.percentile(tail, 50) == 1.0
    assert client.percentile(tail, 99.5) == np.inf


def test_latency_summary_counts_failures():
    win = {"due": np.zeros(4), "done": np.array([0.001, 0.002, 0.003, 0.0]),
           "status": np.array([200, 200, 200, -1]),
           "gen_late": np.zeros(4)}
    s = client.latency_summary(win)
    assert s["failed"] == 1 and s["n"] == 4
    assert s["p50_ms"] == pytest.approx(2.5)
    assert s["p99_ms"] == np.inf


# the whole table of one chip, and one chip's shard of the four-chip cell
@pytest.mark.parametrize("n_rows,blocks", [(40_000, 5), (10_000, 2)])
def test_topk_work_at_the_cells_shapes(n_rows, blocks):
    flops, nbytes = work.topk_batches(32, 1, n_rows, 200)
    assert flops == 2 * 32 * n_rows * 200
    assert nbytes == n_rows * 201 * 4
    f2, b2 = work.topk_batches(64, 2, n_rows, 200)
    assert (f2, b2) == (2 * flops, 2 * nbytes)
    # the streamed slabs behind h2d_mb_per_batch: 8192-row blocks
    assert -(-n_rows // 8192) == blocks


def test_roofline_names_its_bound():
    peaks = spec.peaks("TPU v5 lite")
    flops, nbytes = work.topk_batches(32, 1, 40_000, 200)
    t, bound = work.roofline_s(flops, nbytes, peaks, 1)
    assert bound == "memory"
    assert t == pytest.approx(40_000 * 201 * 4 / 819e9)
    t4, _ = work.roofline_s(flops, nbytes, peaks, 4)
    assert t4 == pytest.approx(t / 4)


def test_transe_flops_per_triple():
    # forward 4d per scored triple, 1 + 32 per positive, backward 2x
    flops = spec.train_reference("transe").flops_per_triple
    assert flops(200, 32) == 3 * 4 * 200 * 33


def test_train_mfu_reads_the_models_flops_from_the_record():
    rec = {"train": {"triples_per_s": 327_460.0}, "chips": 1,
           "work": {"model": "transe", "flops_per_triple": 79_200.0},
           "peaks": spec.peaks("TPU v5 lite")}
    got = spec.metric_reader("train_mfu")(rec)
    assert got == pytest.approx(100 * 79_200 * 327_460 / 197e12)


def test_peaks_unknown_device_is_an_error():
    assert spec.peaks("TPU v5e")["bf16_flops_per_s"] == 197e12
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("mix", ["zipf-topk", "uniform-topk"])
def test_generator_is_seeded(mix):
    m = spec.traffic(mix)
    a = traffic.schedule(m, 40_000, 100.0, 3.0, 2**33 + 5)
    b = traffic.schedule(m, 40_000, 100.0, 3.0, 2**33 + 5)
    c = traffic.schedule(m, 40_000, 100.0, 3.0, 2**33 + 6)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[1], c[1])
    # every seed sends the same amount of work over the same span
    assert len(a[0]) == len(c[0]) == 300
    gaps = [np.sort(np.r_[np.diff(x[0]), 3.0 - x[0][-1]]) for x in (a, c)]
    assert np.allclose(gaps[0], gaps[1])
    assert a[0][0] == 0.0 and a[0][-1] < 3.0
    assert np.all(np.diff(a[0]) > 0)


def test_zipf_skew_and_uniform_spread():
    z = traffic.ranks({"law": "zipf", "s": 1.1}, 40_000, 20_000, 1)
    u = traffic.ranks({"law": "uniform"}, 40_000, 20_000, 1)
    # the top 4096 of 40,000 ranks hold most of a Zipf 1.1 draw
    assert 0.75 < np.mean(z < 4096) < 0.92
    assert 0.08 < np.mean(u < 4096) < 0.13
    with pytest.raises(ValueError):
        traffic.ranks({"law": "bursty"}, 10, 10, 1)


def test_warm_rows_share_the_windows_permutation():
    m = spec.traffic("zipf-topk")
    warm = traffic.warm_rows(m, 40_000, 99)
    _, rows = traffic.schedule(m, 40_000, 500.0, 10.0, 99)
    assert len(warm) == m["warm_cache_requests"]
    hot = traffic.item_permutation(40_000, 99)[:50]
    assert np.isin(hot, warm).mean() > 0.9 and np.isin(hot, rows).mean() > 0.9
    assert len(traffic.warm_rows(spec.traffic("uniform-topk"), 10, 1)) == 0


def test_check_sample_is_seeded_and_bounded():
    a = traffic.check_sample(5000, 1024, 3)
    assert np.array_equal(a, traffic.check_sample(5000, 1024, 3))
    assert len(np.unique(a)) == 1024 and a.max() < 5000
    assert len(traffic.check_sample(10, 1024, 3)) == 10


def test_subseed_takes_wide_seeds():
    seeds = {spec.subseed(s, "traffic") for s in
             (0, 1, 2**31 - 1, 2**31, 2**32 + 1, 2**40, -3)}
    assert len(seeds) == 7
    assert all(0 <= s < 2**31 for s in seeds)
    assert spec.subseed(2**33, "a") != spec.subseed(2**33, "b")
    assert spec.subseed(2**33, "a") == spec.subseed(2**33, "a")


def test_every_cell_config_mix_and_metric_is_found_by_name():
    bench = spec.benchmark()
    for w in bench["workloads"]:
        cell = spec.cell(bench, w["name"])
        assert spec.config(cell["config"])["name"] == cell["config"]
        assert spec.traffic(cell["traffic"])["kind"]
        assert set(cell["limits"])
        e2e = {m["name"] for m in spec.end_to_end(bench, w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = spec.per_layer(bench, w["name"])
        assert layer and all(m["moves"] in e2e for m in layer)
        if spec.traffic(cell["traffic"])["kind"] == "kge_train":
            model = spec.train_reference(spec.config(cell["config"])["model"])
            assert callable(model.init) and callable(model.reference)
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
    for c in bench["configs"]:
        assert (spec.ROOT / c["file"]).is_file()
    with pytest.raises(spec.SpecError):
        spec.workload("no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.metric_reader("no_such_metric")
    with pytest.raises(spec.SpecError, match="references/no_such_model.py"):
        spec.train_reference("no_such_model")


def test_metric_readers_find_nothing_in_an_empty_record():
    bench = spec.benchmark()
    for m in bench["per_layer"]:
        assert spec.metric_reader(m["name"])({"counters": {}}) is None


def test_judge_holds_each_number_to_its_limit():
    ok = checks.judge({"a": 1e-7, "b": 0.0}, {"a": 1e-6, "b": 0})
    assert ok["correct"] and list(ok["checks"]) == ["a", "b"]
    assert not checks.judge({"a": 2e-6, "b": 0.0}, {"a": 1e-6, "b": 0})[
        "correct"]
    assert not checks.judge({"a": float("nan")}, {"a": 1.0})["correct"]
    assert not checks.judge({"a": 0.0}, {"a": 1.0, "b": 0})["correct"]


def test_benchmark_json_keeps_the_contracts_shape():
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for w in bench["workloads"]:
        assert len(w["why"]) <= 200
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def test_host_line_names_steal_collections_and_stalls():
    import gc

    c = hoststats.cpu_counters()
    assert set(c) == {"steal_s", "pressure_s"}
    d = hoststats.counter_delta({"steal_s": 1.5, "pressure_s": None},
                                {"steal_s": 1.0, "pressure_s": 2.0})
    assert d == {"steal_s": 0.5, "pressure_s": None}
    with hoststats.GcPauses() as gcs:
        gc.collect()
    s = gcs.summary()
    assert s["full"] >= 1 and s["longest_ms"] > 0
    line = hoststats.describe(d, s, [(1, 10.25, 0.11), ("parent", 10.5, 0.03)],
                              t0=10.0)
    assert "cpu steal 500.0 ms" in line and "cpu pressure n/a" in line
    assert "p1@+0.25s 110 ms" in line and "parent@+0.50s 30 ms" in line
    assert "stalls" not in hoststats.describe(d, s)


def test_workload_files_hold_only_what_the_harness_reads():
    for path in (spec.HERE / "workloads").glob("*.json"):
        wl = json.loads(path.read_text())
        assert set(wl) <= {"config", "traffic", "chips", "rate_per_s",
                           "limits"}, path.name
