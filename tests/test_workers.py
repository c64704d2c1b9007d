"""Pre-forked multi-process HTTP serving (PR 6 tentpole, layer 3).

One real worker pool (``python -m repro.api.workers``, 2 workers over a
sealed registry) is launched once for the module; the tests drive it
over real sockets: wire byte-parity with the in-process gateway, /stats
merged across workers, cross-process publish→visible via the store
watcher, and SIGKILL crash-restart under client load. Slow tier — the
pool subprocess pays a full jax import per worker.
"""
import http.client
import json
import os
import signal
import subprocess
import sys
import time
import urllib.parse
from pathlib import Path

import numpy as np
import pytest

pytestmark = pytest.mark.slow

REPO = Path(__file__).resolve().parents[1]
N, D = 48, 16


def _publish(root, version, seed):
    from repro.core.registry import EmbeddingRegistry
    rng = np.random.default_rng(seed)
    registry = EmbeddingRegistry(root)
    ids = [f"GO:{i:07d}" for i in range(N)]
    labels = [f"go term {i}" for i in range(N)]
    emb = rng.standard_normal((N, D)).astype(np.float32)
    registry.publish("go", version, "transe", ids, labels, emb,
                     ontology_checksum=f"ck-{version}",
                     hyperparameters={"dim": D})
    registry.seal("go", version)
    return ids, emb


def _get(port, path, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _stats(port):
    status, body = _get(port, "/stats")
    assert status == 200
    return json.loads(body)


def test_probe_platform_asks_a_child():
    from repro.api.workers import probe_platform
    assert probe_platform() == "cpu"          # the suite runs JAX_PLATFORMS=cpu


def test_pool_refuses_several_workers_off_cpu(tmp_path, monkeypatch):
    """Each worker would need the accelerator, which one process owns:
    the pool refuses at start instead of crash-looping its workers."""
    from repro.api import workers
    monkeypatch.setattr(workers, "probe_platform", lambda: "tpu")
    pool = workers.WorkerPool(tmp_path, workers=2,
                              state_dir=tmp_path / "state")
    with pytest.raises(RuntimeError, match="B1"):
        pool.start()
    assert pool.pids() == [] and pool.port is None


@pytest.fixture(scope="module")
def pool(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("wreg"))
    ids, emb = _publish(root, "2024-01", seed=1)
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.api.workers", "--registry", root,
         "--workers", "2", "--watch-interval-ms", "100",
         "--stats-interval-ms", "200"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        cwd=str(REPO))
    line = proc.stdout.readline().strip()
    if not line.startswith("READY"):
        err = proc.stderr.read()
        proc.kill()
        raise RuntimeError(f"pool failed to start: {line!r}\n{err}")
    port = int(line.split("port=")[1].split()[0])
    yield {"proc": proc, "port": port, "root": root, "ids": ids, "emb": emb}
    proc.send_signal(signal.SIGTERM)
    try:
        proc.communicate(timeout=30)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()


def test_wire_parity_with_inprocess_gateway(pool):
    """Bodies over the pool's socket are byte-identical to the wire dicts
    ``Gateway.handle`` produces in-process over the same registry."""
    from repro.api import Gateway
    from repro.core.registry import EmbeddingRegistry
    from repro.core.serving import ServingEngine
    ids = pool["ids"]
    paths = [f"/get-vector/go/transe?query={ids[0]}",
             f"/sim/go/transe?a={ids[1]}&b={ids[2]}",
             f"/closest-concepts/go/transe?query={ids[3]}&k=5",
             "/download/go/transe?offset=0&limit=4",
             "/autocomplete/go/transe?prefix=go%20term%201&limit=5",
             "/versions/go"]
    gw = Gateway(ServingEngine(EmbeddingRegistry(pool["root"])))
    try:
        for path in paths:
            status, body = _get(pool["port"], path)
            assert status == 200, (path, body[:200])
            route, _, query = path.partition("?")
            payload = {}
            for k, v in urllib.parse.parse_qsl(query):
                payload[k] = int(v) if v.isdigit() else v
            expect = json.dumps(gw.handle(route, payload)).encode()
            assert body == expect, path
    finally:
        gw.close()


def test_stats_merged_across_workers(pool):
    """/stats answered by either worker reports the whole pool: a
    ``workers`` block with both pids and counters summed from the
    per-worker state dumps."""
    ids = pool["ids"]
    for i in range(12):
        status, _ = _get(pool["port"],
                         f"/get-vector/go/transe?query={ids[i % N]}")
        assert status == 200
    deadline = time.time() + 15
    while time.time() < deadline:
        st = _stats(pool["port"])
        w = st.get("workers", {})
        if w.get("count") == 2 and st["gateway"]["requests"] >= 12:
            break
        time.sleep(0.2)
    assert w["count"] == 2
    assert len(w["pids"]) == 2
    assert st["type"] == "stats_response"
    assert st["gateway"]["requests"] >= 12      # summed, not per-worker
    assert "latency" in st and "scheduler" in st

    # transport-level 304s are pool-visible too (PR 7 satellite): a
    # conditional re-fetch answered before dispatch must still surface
    # in the merged workers.http block, with a latency histogram
    conn = http.client.HTTPConnection("127.0.0.1", pool["port"], timeout=30)
    try:
        conn.request("GET", "/download/go/transe?limit=3")
        resp = conn.getresponse()
        resp.read()
        etag = resp.getheader("ETag")
        conn.request("GET", "/download/go/transe?limit=3",
                     headers={"If-None-Match": etag})
        resp = conn.getresponse()
        resp.read()
        assert resp.status == 304
    finally:
        conn.close()
    deadline = time.time() + 15
    nm, lat = 0, None
    while time.time() < deadline:
        http_block = _stats(pool["port"])["workers"].get("http", {})
        nm = http_block.get("not_modified", 0)
        lat = (http_block.get("latency_ms") or {}).get("not_modified")
        # the serving worker's own /stats sees it live; a sibling's view
        # waits for the next periodic state dump — poll either way
        if nm >= 1 and lat and lat.get("count", 0) >= 1:
            break
        time.sleep(0.2)
    assert nm >= 1
    assert lat["count"] >= 1 and lat["p50_ms"] >= 0


def test_publish_visible_across_processes(pool):
    """A publish+seal from THIS process becomes servable in the pool's
    workers without any signal besides the store itself."""
    ids, emb2 = _publish(pool["root"], "2024-02", seed=2)
    deadline = time.time() + 20
    latest = None
    while time.time() < deadline:
        _, body = _get(pool["port"], "/versions/go")
        latest = json.loads(body).get("latest")
        if latest == "2024-02":
            break
        time.sleep(0.1)
    assert latest == "2024-02"
    # and the vectors served are the new version's, bit-exact
    _, body = _get(pool["port"], f"/get-vector/go/transe?query={ids[5]}")
    got = np.asarray(json.loads(body)["vector"], dtype=np.float32)
    np.testing.assert_array_equal(got, emb2[5])


def test_sigkill_worker_is_restarted_under_load(pool):
    """SIGKILL one worker mid-traffic: the supervisor respawns it, the
    pool keeps answering (at most one retryable client error), and
    /stats shows the restart."""
    ids = pool["ids"]
    victim = _stats(pool["port"])["workers"]["pids"][0]
    os.kill(victim, signal.SIGKILL)
    errors = 0
    for i in range(40):
        try:
            status, _ = _get(pool["port"],
                             f"/sim/go/transe?a={ids[i % N]}&b={ids[0]}",
                             timeout=10)
            if status != 200:
                errors += 1
        except OSError:
            errors += 1
        time.sleep(0.05)
    assert errors <= 1, f"{errors} client errors after SIGKILL"
    deadline = time.time() + 20
    while time.time() < deadline:
        w = _stats(pool["port"])["workers"]
        if w["count"] == 2 and w["restarts"] >= 1 \
                and victim not in w["pids"]:
            break
        time.sleep(0.2)
    assert w["count"] == 2
    assert w["restarts"] >= 1
    assert victim not in w["pids"]
