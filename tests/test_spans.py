"""Spans and counters inside the serving path (``repro.core.metrics.span``).

Off a profiler a span only times into its counter; inside a profiler
session each one is a host event of the trace, named cleanly.  The
counters they feed (``BatchScheduler.stats``, ``kernels.ops.stream_stats``,
the HTTP server's ``http_stats``) travel in ``/stats`` and merge across a
worker pool.  Fast tier — tiny tables on the CPU.
"""
import json
import threading
import urllib.request

import jax
import numpy as np
import pytest

from repro.api import Gateway, serve_http
from repro.api.workers import merge_stats_wires
from repro.core import metrics
from repro.core.serving import (BatchScheduler, ServingEngine, SimRequest,
                                TopKRequest)
from repro.kernels import ops as kops

N, D = 40, 12
#: slab rows that split the N-row table into three streamed slabs
SLAB = 16


def _publish(registry, ontology="go", version="2024-01", model="transe"):
    rng = np.random.default_rng(3)
    ids = [f"{ontology.upper()}:{i:07d}" for i in range(N)]
    labels = [f"{ontology} term {i}" for i in range(N)]
    emb = rng.standard_normal((N, D)).astype(np.float32)
    registry.publish(ontology, version, model, ids, labels, emb,
                     ontology_checksum=f"ck-{version}",
                     hyperparameters={"dim": D})
    return ids


class _Spy:
    """Stands in for ``TraceAnnotation`` and records what it was given."""

    made = []

    def __init__(self, name, **meta):
        _Spy.made.append((name, meta))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_span_without_a_profiler_only_times(monkeypatch):
    with metrics.span("warm-up"):
        pass                            # binds the profiler's hooks
    _Spy.made = []
    monkeypatch.setattr(metrics, "_annotation", _Spy)
    stats = {"busy_s": 0.5}
    with metrics.span("layer.work", stats, "busy_s", rid=7):
        pass
    assert _Spy.made == []
    assert stats["busy_s"] > 0.5
    lock = threading.Lock()
    with metrics.span("layer.work", stats, "other_s", lock=lock):
        pass
    assert stats["other_s"] > 0.0 and not lock.locked()
    with metrics.span("layer.work"):
        pass
    assert set(stats) == {"busy_s", "other_s"} and _Spy.made == []
    # with nothing to record, every span is one shared object
    assert metrics.span("a") is metrics.span("b", rid=3)


def test_span_inside_a_profiler_annotates_with_metadata(monkeypatch,
                                                        tmp_path):
    with metrics.span("warm-up"):
        pass
    _Spy.made = []
    monkeypatch.setattr(metrics, "_annotation", _Spy)
    stats = {}
    jax.profiler.start_trace(str(tmp_path))
    try:
        with metrics.span("layer.work", stats, "busy_s", rid=7):
            pass
    finally:
        jax.profiler.stop_trace()
    assert _Spy.made == [("layer.work", {"rid": 7})]
    assert stats["busy_s"] >= 0.0


def test_span_times_even_when_the_block_raises():
    stats = {}
    with pytest.raises(ValueError):
        with metrics.span("layer.work", stats, "busy_s"):
            raise ValueError("boom")
    assert stats["busy_s"] >= 0.0


def _stream_deltas(before):
    after = kops.stream_counts()
    return {k: after[k] - before[k] for k in ("calls", "blocks", "stage_s",
                                              "put_s")}


def test_flush_counts_queue_wait_batch_index_and_slab_time(registry,
                                                           monkeypatch):
    ids = _publish(registry)
    monkeypatch.setattr(kops, "STREAM_BLOCK_ROWS", SLAB)
    engine = ServingEngine(registry, cache_capacity=2)
    sched = BatchScheduler(engine, max_batch=4)
    engine._index("go", "transe")              # build outside the window
    before = kops.stream_counts()
    tickets = [sched.submit(TopKRequest("go", "transe", ids[i], 5))
               for i in range(6)]
    bad = sched.submit(TopKRequest("go", "transe", "GO:nope", 5))
    sim = sched.submit(SimRequest("go", "transe", ids[0], ids[1]))
    results = sched.flush()
    assert all(len(results[t.id]) == 5 for t in tickets)
    assert bad.exception() and isinstance(sim.result(), float)
    st = sched.stats
    # six top-k tickets reached a kernel batch: two chunks of max_batch 4
    assert st["queued"] == 6
    assert st["batches"] - st["sim_batches"] == 2
    assert st["queue_wait_s"] > 0.0
    stream = _stream_deltas(before)
    assert stream["calls"] == 2 and stream["blocks"] == 2 * 3
    assert st["batch_s"] >= st["topk_s"] >= \
        stream["stage_s"] + stream["put_s"] >= 0.0
    assert stream["stage_s"] > 0.0 and stream["put_s"] > 0.0


def test_stream_counters_stay_exact_under_concurrent_streams():
    """The flush loop and the job executor stream at once: no update of
    ``stream_stats`` may be lost."""
    import sys

    rng = np.random.default_rng(0)
    table = rng.standard_normal((N, D)).astype(np.float32)
    q = table[:2] / np.linalg.norm(table[:2], axis=1, keepdims=True)
    kops.topk_cosine(q, table, 3, block_rows=SLAB)     # compile once
    before = kops.stream_counts()

    def work():
        for _ in range(10):
            kops.topk_cosine(q, table, 3, block_rows=SLAB)

    threads = [threading.Thread(target=work) for _ in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    d = _stream_deltas(before)
    assert d["calls"] == 80 and d["blocks"] == 80 * 3


@pytest.fixture()
def served(registry, monkeypatch):
    ids = _publish(registry)
    monkeypatch.setattr(kops, "STREAM_BLOCK_ROWS", SLAB)
    engine = ServingEngine(registry, cache_capacity=2)
    gateway = Gateway(engine, max_batch=4, flush_after_ms=1.0,
                      result_cache_entries=0)
    server = serve_http(gateway, port=0)
    yield server, gateway, ids
    server.close()
    gateway.close()


def _closest(server, ident):
    url = f"{server.url}/closest-concepts/go/transe?k=3&query={ident}"
    with urllib.request.urlopen(url, timeout=30) as r:
        return json.loads(r.read())


def test_profiler_session_holds_the_serving_spans(served, tmp_path):
    from benchmarks.chip import tracing

    server, gateway, ids = served
    _closest(server, ids[0])                       # compile outside
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for i in range(1, 5):
            assert len(_closest(server, ids[i])["results"]) == 3
    finally:
        jax.profiler.stop_trace()
    names = {name for name, _, _ in
             tracing.extract(tracing.find_xplane(str(tmp_path)))["host"]}
    for want in ("http.request", "http.send", "gateway.route",
                 "scheduler.wait", "scheduler.batch", "index.topk",
                 "index.queries", "index.fetch", "index.decode",
                 "stream.stage", "stream.put", "stream.step",
                 "scheduler.idle"):
        assert want in names, want
    assert not any(n.startswith(("scheduler.batch", "scheduler.wait"))
                   and n not in ("scheduler.batch", "scheduler.wait")
                   for n in names)


def test_stats_carry_the_new_counters(served):
    server, gateway, ids = served
    for i in range(3):
        _closest(server, ids[i])
    with urllib.request.urlopen(server.url + "/stats", timeout=30) as r:
        body = json.loads(r.read())
    sched = body["scheduler"]
    assert sched["queued"] == 3
    assert sched["batch_s"] >= sched["topk_s"] > 0.0
    assert sched["queue_wait_s"] > 0.0
    assert body["stream"]["stage_s"] > 0.0 and body["stream"]["put_s"] > 0.0
    http = body["http"]
    # the /stats request itself is still open when its body is built
    assert http["request_s"] >= http["send_s"] > 0.0


def test_merge_stats_wires_sums_seconds_and_keeps_peaks():
    def wire(wait, stage, peak):
        return {"type": "stats_response",
                "scheduler": {"queued": 2, "queue_wait_s": wait,
                              "batch_s": 0.5, "topk_s": 0.25},
                "cache": {}, "gateway": {}, "latency": {},
                "stream": {"calls": 1, "stage_s": stage, "put_s": 0.125,
                           "peak_block_bytes": peak}}

    merged = merge_stats_wires([wire(0.5, 0.25, 100), wire(1.0, 0.5, 300)])
    assert merged["scheduler"]["queue_wait_s"] == 1.5
    assert merged["scheduler"]["queued"] == 4
    assert merged["scheduler"]["batch_s"] == 1.0
    assert merged["stream"] == {"calls": 2, "stage_s": 0.75, "put_s": 0.25,
                                "peak_block_bytes": 300}
