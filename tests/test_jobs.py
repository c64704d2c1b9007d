"""Async batch-analytics job subsystem (PR 9 tentpole).

Covers the job lifecycle end to end: submit → poll → page/stream result
for all three workloads (bulk kNN join, cross-version drift, model
compare), result parity with the serial per-query oracle, the error
taxonomy (JOB_NOT_FOUND / JOB_CANCELLED / BAD_REQUEST / OVERLOADED)
counted exactly once through both ``Gateway.handle`` and HTTP, a
16-client poll storm against one running bulk job (exactly-once
materialization, monotone progress), cancellation mid-slab, and — slow
tier — a SIGKILL'd multi-process worker whose orphaned job reads FAILED
instead of hanging pollers.
"""
import http.client
import json
import os
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from repro.api import ApiError, Gateway, serve_http
from repro.core.serving import ServingEngine

REPO = Path(__file__).resolve().parents[1]
N, D = 40, 12


def _publish(registry, ontology, version, model="transe", n=N, seed=0):
    rng = np.random.default_rng(seed)
    ids = [f"{ontology.upper()}:{i:07d}" for i in range(n)]
    labels = [f"{ontology} term {i}" for i in range(n)]
    emb = rng.standard_normal((n, D)).astype(np.float32)
    registry.publish(ontology, version, model, ids, labels, emb,
                     ontology_checksum=f"ck-{version}-{seed}",
                     hyperparameters={"dim": D})
    return ids


@pytest.fixture()
def gw(registry):
    ids = _publish(registry, "go", "2024-01", seed=1)
    _publish(registry, "go", "2024-02", seed=2)
    engine = ServingEngine(registry, cache_capacity=4)
    gateway = Gateway(engine)
    yield gateway, engine, ids
    gateway.close()


def _slow_gw(registry, ids=None, **kw):
    """A gateway whose jobs crawl: tiny slabs + a large inter-slab yield
    make RUNNING observable and give cancels/storms slabs to land in."""
    engine = ServingEngine(registry, cache_capacity=4)
    kw.setdefault("jobs_slab", 4)
    kw.setdefault("jobs_yield_s", 0.03)
    return Gateway(engine, **kw)


# ------------------------- workload correctness ------------------------ #
def test_knn_join_matches_serial_oracle(gw):
    gateway, engine, ids = gw
    sub = gateway.submit_job("knn-join", "go", model="transe",
                             classes=ids, k=5)
    assert sub.state in ("PENDING", "RUNNING")
    st = gateway.job_wait(sub.job_id, timeout=60)
    assert st.state == "DONE" and st.progress == 1.0
    assert st.total == len(ids) and st.wall_s is not None
    assert st.summary["n_queries"] == len(ids)
    page = gateway.job_result(sub.job_id, limit=len(ids))
    assert page.total == len(ids) and page.next_offset is None
    idx = engine._index("go", "transe")
    for ident, neighbors in page.rows:
        oracle = idx.top_k([ident], k=5)[0]
        # ids exact; scores to float32 rounding — the join scores a
        # 256-row slab, the oracle one row, and the two matmuls may
        # round the last ulp differently
        assert [n[0] for n in neighbors] == [c.identifier for c in oracle]
        np.testing.assert_allclose([n[1] for n in neighbors],
                                   [c.score for c in oracle],
                                   rtol=0, atol=1e-6)


def test_drift_matches_manual_jaccard(gw):
    gateway, engine, ids = gw
    k = 5
    sub = gateway.submit_job("drift", "go", model="transe", k=k)
    st = gateway.job_wait(sub.job_id, timeout=60)
    assert st.state == "DONE"
    # default pair: previous release vs latest
    assert st.version == "2024-01" and st.version_b == "2024-02"
    page = gateway.job_result(sub.job_id, limit=N)
    idx_a = engine._index("go", "transe", "2024-01")
    idx_b = engine._index("go", "transe", "2024-02")
    got = dict(page.rows)
    for ident in ids:
        sa = {c.identifier for c in idx_a.top_k([ident], k=k)[0]}
        sb = {c.identifier for c in idx_b.top_k([ident], k=k)[0]}
        expect = len(sa & sb) / len(sa | sb)
        assert got[ident] == pytest.approx(expect)
    assert st.summary["n_common"] == N
    # the summary value is rounded for the wire — compare to its precision
    assert st.summary["mean_jaccard"] == pytest.approx(
        float(np.mean(list(got.values()))), abs=1e-6)


def test_compare_without_stored_graph_reports_skip(gw):
    gateway, _, _ = gw
    sub = gateway.submit_job("compare", "go")
    st = gateway.job_wait(sub.job_id, timeout=60)
    assert st.state == "DONE"
    page = gateway.job_result(sub.job_id)
    # no graph stored for the synthetic publish: every model row is
    # present but metric-less, and the summary says why
    assert [r[0] for r in page.rows] == ["transe"]
    assert page.rows[0][1] is None
    assert st.summary["skipped"] == 1 and "note" in st.summary


def test_submit_validation(gw):
    gateway, _, ids = gw
    with pytest.raises(ApiError) as e:
        gateway.submit_job("frobnicate", "go")
    assert e.value.code == "BAD_REQUEST"
    with pytest.raises(ApiError) as e:
        gateway.submit_job("knn-join", "go", model="transe", classes=[])
    assert e.value.code == "BAD_REQUEST"
    with pytest.raises(ApiError) as e:
        gateway.submit_job("knn-join", "go", model="nope", classes=ids[:2])
    assert e.value.code == "UNKNOWN_MODEL"
    # unknown classes fail the job (not the submit — resolution happens
    # on the executor), with the missing list in the error
    sub = gateway.submit_job("knn-join", "go", model="transe",
                             classes=["GO:9999999"])
    st = gateway.job_wait(sub.job_id, timeout=30)
    assert st.state == "FAILED" and "UNKNOWN_CLASS" in st.error


# --------------------------- error taxonomy ---------------------------- #
def test_taxonomy_through_handle_counted_once(gw):
    gateway, _, ids = gw
    wire = gateway.handle("jobs/j0-404")
    assert wire["type"] == "error" and wire["code"] == "JOB_NOT_FOUND"
    assert wire["status"] == 404
    sub = gateway.submit_job("knn-join", "go", model="transe",
                             classes=ids[:3], k=3)
    gateway.job_wait(sub.job_id, timeout=30)
    wire = gateway.handle(f"jobs/{sub.job_id}/cancel")
    assert wire["code"] == "BAD_REQUEST" and wire["status"] == 400
    by_code = gateway.stats().gateway["by_code"]
    assert by_code["JOB_NOT_FOUND"] == 1
    assert by_code["BAD_REQUEST"] == 1


def test_result_of_cancelled_job_is_job_cancelled(registry):
    ids = _publish(registry, "go", "2024-01", seed=1)
    gateway = _slow_gw(registry)
    try:
        sub = gateway.submit_job("knn-join", "go", model="transe",
                                 classes=ids, k=3)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            st = gateway.job_status(sub.job_id)
            if st.state == "RUNNING" and st.progress > 0:
                break
            time.sleep(0.002)
        else:
            pytest.fail("job never observed RUNNING")
        gateway.job_cancel(sub.job_id)
        st = gateway.job_wait(sub.job_id, timeout=30)
        # cancelled mid-slab: terminal, partial progress, no result
        assert st.state == "CANCELLED"
        assert 0 < st.progress < 1.0
        with pytest.raises(ApiError) as e:
            gateway.job_result(sub.job_id)
        assert e.value.code == "JOB_CANCELLED" and e.value.status == 409
    finally:
        gateway.close()


def test_queue_overflow_fast_rejects(registry):
    ids = _publish(registry, "go", "2024-01", seed=1)
    gateway = _slow_gw(registry, max_jobs_queued=1)
    try:
        first = gateway.submit_job("knn-join", "go", model="transe",
                                   classes=ids, k=3)
        # wait for the executor to claim the first job, so the next
        # submit is the only PENDING one and the one after must reject
        deadline = time.monotonic() + 30
        while gateway.job_status(first.job_id).state == "PENDING":
            assert time.monotonic() < deadline
            time.sleep(0.002)
        gateway.submit_job("knn-join", "go", model="transe",
                           classes=ids[:4], k=3)
        with pytest.raises(ApiError) as e:
            gateway.submit_job("knn-join", "go", model="transe",
                               classes=ids[:4], k=3)
        assert e.value.code == "OVERLOADED" and e.value.status == 429
        assert e.value.details["retry_after_s"] > 0
        assert gateway.jobs.stats()["rejected_overloaded"] == 1
    finally:
        gateway.close()


# ------------------------ poll storm / cancellation -------------------- #
def test_poll_storm_exactly_once_and_monotone_progress(registry):
    ids = _publish(registry, "go", "2024-01", seed=1)
    gateway = _slow_gw(registry)
    try:
        sub = gateway.submit_job("knn-join", "go", model="transe",
                                 classes=ids, k=5)
        results, errs = [], []
        lock = threading.Lock()

        def poller():
            try:
                seen = []
                while True:
                    st = gateway.job_status(sub.job_id)
                    seen.append(st.progress)
                    if st.state in ("DONE", "FAILED", "CANCELLED"):
                        break
                    time.sleep(0.001)
                # progress is monotone non-decreasing for every client
                assert seen == sorted(seen)
                assert st.state == "DONE"
                page = gateway.job_result(sub.job_id, limit=N)
                with lock:
                    results.append(json.dumps(page.rows, sort_keys=True))
            except Exception as e:                 # pragma: no cover
                with lock:
                    errs.append(e)

        threads = [threading.Thread(target=poller) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errs
        # exactly-once materialization: one completed run, every client
        # read the same bytes
        assert len(set(results)) == 1 and len(results) == 16
        assert gateway.jobs.stats()["completed"] == 1
        assert gateway.job_status(sub.job_id).summary["slabs"] == \
            (N + 3) // 4
    finally:
        gateway.close()


# ------------------------------ HTTP layer ----------------------------- #
def _http(port, method, path, body=None, headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request(method, path,
                     body=json.dumps(body) if body is not None else None,
                     headers=headers or {})
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def test_http_job_roundtrip_etag_stream_and_taxonomy(gw):
    gateway, _, ids = gw
    server = serve_http(gateway, port=0)
    try:
        port = server.port
        st, _, body = _http(port, "POST", "/jobs/submit",
                            {"kind": "knn-join", "ontology": "go",
                             "model": "transe", "classes": ids[:8], "k": 3})
        assert st == 200
        jid = json.loads(body)["job_id"]
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            st, _, body = _http(port, "GET", f"/jobs/{jid}")
            if json.loads(body)["state"] == "DONE":
                break
            time.sleep(0.01)
        assert json.loads(body)["state"] == "DONE"
        # page + strong ETag; If-None-Match revalidates to a bodyless 304
        st, hdr, body = _http(port, "GET", f"/jobs/{jid}/result?limit=5")
        assert st == 200 and hdr.get("ETag")
        page = json.loads(body)
        assert page["type"] == "job_result_page" and page["total"] == 8
        assert len(page["rows"]) == 5 and page["next_offset"] == 5
        st2, hdr2, body2 = _http(port, "GET", f"/jobs/{jid}/result?limit=5",
                                 headers={"If-None-Match": hdr["ETag"]})
        assert st2 == 304 and body2 == b""
        assert hdr2.get("ETag") == hdr["ETag"]
        # chunked stream: the whole row set as one JSON array
        st3, hdr3, body3 = _http(port, "GET",
                                 f"/jobs/{jid}/result?stream=true")
        assert st3 == 200
        assert hdr3.get("X-Bio-KGvec2go-Kind") == "knn-join"
        rows = json.loads(body3)
        assert rows == page["rows"] + json.loads(
            _http(port, "GET", f"/jobs/{jid}/result?offset=5")[2])["rows"]
        # taxonomy over HTTP: real status lines, counted exactly once
        before = json.loads(_http(port, "GET", "/stats")[2])
        st4, _, body4 = _http(port, "GET", "/jobs/j0-404")
        assert st4 == 404
        assert json.loads(body4)["code"] == "JOB_NOT_FOUND"
        st5, _, body5 = _http(port, "POST", f"/jobs/{jid}/cancel", {})
        assert st5 == 400
        assert json.loads(body5)["code"] == "BAD_REQUEST"
        after = json.loads(_http(port, "GET", "/stats")[2])
        b0 = before["gateway"]["by_code"]
        b1 = after["gateway"]["by_code"]
        assert b1.get("JOB_NOT_FOUND", 0) == b0.get("JOB_NOT_FOUND", 0) + 1
        assert b1.get("BAD_REQUEST", 0) == b0.get("BAD_REQUEST", 0) + 1
        assert after["gateway"]["jobs"]["completed"] == 1
    finally:
        server.close()


def test_async_gateway_submit_wait_result(gw):
    import asyncio

    from repro.api.aio import AsyncGateway
    gateway, _, ids = gw

    async def main():
        ag = AsyncGateway(gateway)
        sub = await ag.submit_job("knn-join", "go", model="transe",
                                  classes=ids[:6], k=3)
        st = await ag.job_wait(sub.job_id, timeout=60)
        page = await ag.job_result(sub.job_id)
        listed = await ag.jobs_list()
        return st, page, listed

    st, page, listed = asyncio.run(main())
    assert st.state == "DONE"
    assert page.total == 6 and len(page.rows) == 6
    assert [j.job_id for j in listed.jobs] == [st.job_id]


# ----------------------- multi-process orphan rule --------------------- #
@pytest.mark.slow
def test_sigkilled_worker_reports_orphaned_job_failed(tmp_path):
    """SIGKILL the worker that owns a RUNNING job: a surviving sibling
    (or the supervisor's replacement) must answer polls with FAILED —
    never hang them, never resurrect the job."""
    from repro.core.registry import EmbeddingRegistry
    n = 256
    rng = np.random.default_rng(0)
    root = tmp_path / "reg"
    registry = EmbeddingRegistry(root)
    ids = [f"GO:{i:07d}" for i in range(n)]
    registry.publish("go", "2024-01", "transe", ids,
                     [f"t{i}" for i in range(n)],
                     rng.standard_normal((n, D)).astype(np.float32),
                     ontology_checksum="ck", hyperparameters={"dim": D})
    registry.seal("go", "2024-01")
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.api.workers", "--registry", str(root),
         "--workers", "2", "--stats-interval-ms", "200"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        cwd=str(REPO))
    try:
        line = proc.stdout.readline().strip()
        assert line.startswith("READY"), proc.stderr.read()
        port = int(line.split("port=")[1].split()[0])

        def poll(path, method="GET", body=None):
            # the killed worker's accept queue drops connections; retry
            # onto a live sibling
            for _ in range(50):
                try:
                    return _http(port, method, path, body)
                except OSError:
                    time.sleep(0.05)
            raise AssertionError("pool stopped answering")

        # a join big enough to still be RUNNING when the SIGKILL lands
        st, _, body = poll("/jobs/submit", "POST",
                           {"kind": "knn-join", "ontology": "go",
                            "model": "transe", "classes": ids * 250,
                            "k": 10})
        assert st == 200, body
        job = json.loads(body)
        jid, owner = job["job_id"], job["owner_pid"]
        assert owner in (int(p) for p in
                         line.split("pids=")[1].split()[0].split(","))
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            state = json.loads(poll(f"/jobs/{jid}")[2])["state"]
            if state == "RUNNING":
                break
            assert state == "PENDING", state
            time.sleep(0.01)
        os.kill(owner, signal.SIGKILL)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            st, _, body = poll(f"/jobs/{jid}")
            job = json.loads(body)
            if job["state"] == "FAILED":
                break
            time.sleep(0.05)
        assert job["state"] == "FAILED"
        assert "died" in job["error"]
        # the failure is sticky: a later poll still reads FAILED, and
        # the result route answers the structured per-state error
        assert json.loads(poll(f"/jobs/{jid}")[2])["state"] == "FAILED"
        st, _, body = poll(f"/jobs/{jid}/result")
        assert st == 400
        assert json.loads(body)["details"]["state"] == "FAILED"
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            proc.kill()
