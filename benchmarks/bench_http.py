"""HTTP layer benchmark: the stdlib service front end vs the in-process
gateway under 16 concurrent clients, plus the conditional-GET fast path.

Three measurements over the same top-k workload:

  * gateway-inproc  — 16 threads call ``gw.closest_concepts`` directly
    (PR 4's batched mode: tickets + the background flush loop). This is
    the ceiling: no sockets, no JSON re-parse.
  * http            — the same 16 clients, each holding ONE persistent
    keep-alive ``http.client.HTTPConnection`` to a
    ``ThreadingHTTPServer`` over the *same* gateway, so the scheduler
    coalesces across sockets exactly as it does across threads. The
    clients run in a SEPARATE process: real clients do not share the
    server's GIL, and billing the server for client-side response
    parsing in the same interpreter would understate it ~2x.
  * etag-304        — single client re-fetching a pinned download page
    with ``If-None-Match``: the 304 path (no gateway, no index) vs the
    full 200 page fetch, q/s each.

Emits ``benchmarks/results/BENCH_http.json``.

Usage:
    PYTHONPATH=src python -m benchmarks.bench_http [--fast]

Acceptance floor (PR 5): HTTP >= 0.5x the in-process gateway q/s at 16
clients at full size — the transport tax (socket + HTTP parse + JSON
codec) must stay under half the throughput, which it only does if
keep-alive and cross-socket coalescing actually work. At --fast CI size
the floor is 0.2x: with a 2k-class table the kernel work per request is
so small that the constant per-request transport cost dominates both
sides of the ratio (and the 2-core CI box runs client and server
processes on the same silicon), so the CI floor only catches
"keep-alive or coalescing stopped working" regressions; measured
full-size numbers are the recorded trajectory.
"""
from __future__ import annotations

import argparse
import http.client
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "src"))

RESULTS = REPO / "benchmarks" / "results"
FLOOR = 0.5          # http q/s vs in-process gateway q/s, 16 clients
CI_FLOOR = 0.2       # --fast: transport tax dominates at tiny kernel size

# ---- multi-process (--workers) floors -------------------------------- #
# The 1.5x MP-vs-SP floor presumes the workers can actually run in
# parallel: it applies only when the box has at least workers+1 cores
# (N servers + the client fleet process). On smaller machines — the
# 1-core container this repo often runs in — N processes time-slice one
# core, MP physically cannot beat SP, and the measured ratio swings
# 0.5-0.9x run to run; the speedup is then recorded but not gated
# (parity, table sharing and publish-visibility still are).
MP_FLOOR = 1.5       # full size, enough cores
MP_CI_FLOOR = 1.05   # --fast, enough cores: tiny kernels, transport-bound
#: pool-wide PSS of the ``table.f32`` file mapping may exceed one file
#: by at most this factor. PSS bills a page shared by M workers 1/M to
#: each, so N workers mmap'ing one table sum to ~one table — copies
#: (anon memory, or COW'd private pages) would sum to ~N tables. This
#: is the zero-copy gate: per-mapping, so it is immune to the ~125MB of
#: private XLA/interpreter footprint that dominates whole-process PSS.
MP_TABLE_PSS_RATIO = 1.1

#: the out-of-process client fleet: argv = port clients per_client n k,
#: stdout = one JSON line {"wall": s, "lat": [s, ...]}
_CLIENT_DRIVER = r"""
import http.client, json, random, sys, threading, time
port, clients, per, n, k = (int(a) for a in sys.argv[1:6])
ids = [f"GO:{i:07d}" for i in range(n)]
lat, errors, lock = [], [], threading.Lock()
barrier = threading.Barrier(clients + 1)

def worker(cix):
    r = random.Random(100 + cix)
    mine = []
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        barrier.wait()
        for _ in range(per):
            q = ids[r.randrange(n)]
            t0 = time.perf_counter()
            conn.request("GET",
                         f"/closest-concepts/go/transe?query={q}&k={k}")
            resp = conn.getresponse()
            body = resp.read()
            mine.append(time.perf_counter() - t0)
            assert resp.status == 200, body[:200]
        conn.close()
    except Exception as e:
        # a dead client must fail the whole measurement, not quietly
        # inflate q/s by shortening the wall clock
        with lock:
            errors.append(f"client {cix}: {e!r}")
    with lock:
        lat.extend(mine)

threads = [threading.Thread(target=worker, args=(i,)) for i in range(clients)]
for t in threads:
    t.start()
barrier.wait()
t0 = time.perf_counter()
for t in threads:
    t.join()
if errors or len(lat) != clients * per:
    print("\n".join(errors) or f"only {len(lat)} requests completed",
          file=sys.stderr)
    sys.exit(1)
print(json.dumps({"wall": time.perf_counter() - t0, "lat": lat}))
"""


def _percentiles(lat_s):
    lat_ms = np.asarray(lat_s) * 1e3
    return (round(float(np.percentile(lat_ms, 50)), 3),
            round(float(np.percentile(lat_ms, 99)), 3))


def run(fast: bool = False, clients: int = 16, max_batch: int = 64,
        flush_after_ms: float = 2.0,
        total_requests: int | None = None) -> dict:
    from repro.api import Gateway, serve_http
    from repro.core.registry import EmbeddingRegistry
    from repro.core.serving import BatchScheduler, ServingEngine, TopKRequest

    n = 2_000 if fast else 20_000          # paper: GO > 40k classes
    d, k = 200, 10
    total = total_requests or (512 if fast else 2_048)
    per_client = total // clients
    total = per_client * clients
    rng = np.random.default_rng(0)

    with tempfile.TemporaryDirectory() as td:
        registry = EmbeddingRegistry(td)
        ids = [f"GO:{i:07d}" for i in range(n)]
        labels = [f"synthetic term {i}" for i in range(n)]
        emb = rng.standard_normal((n, d)).astype(np.float32)
        registry.publish("go", "2025-01", "transe", ids, labels, emb,
                         ontology_checksum="bench", hyperparameters={"dim": d})
        engine = ServingEngine(registry)

        # jit-warm every power-of-two bucket shape either mode can hit
        warm = BatchScheduler(engine, max_batch=max_batch)
        b = 1
        while b <= max_batch:
            for _ in range(b):
                warm.submit(TopKRequest("go", "transe",
                                        ids[int(rng.integers(n))], k))
            warm.flush()
            b <<= 1

        gw = Gateway(engine, max_batch=max_batch,
                     flush_after_ms=flush_after_ms)
        out = {"n_classes": n, "dim": d, "k": k, "clients": clients,
               "max_batch": max_batch, "flush_after_ms": flush_after_ms,
               "total_requests": total, "modes": []}

        def fanout(worker):
            lat, failures, lock = [], [], threading.Lock()
            barrier = threading.Barrier(clients + 1)

            def client(cix):
                r = np.random.default_rng(100 + cix)
                barrier.wait()
                try:
                    mine = worker(cix, r)
                except Exception as e:
                    with lock:
                        failures.append(f"client {cix}: {e!r}")
                    return
                with lock:
                    lat.extend(mine)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(clients)]
            for t in threads:
                t.start()
            barrier.wait()
            t0 = time.perf_counter()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            # a dead client shortens the wall clock — failing loudly is
            # the only way the q/s ratio stays meaningful
            assert not failures, failures
            assert len(lat) == total, f"only {len(lat)}/{total} completed"
            return wall, lat

        # ---- mode 1: in-process batched gateway (the ceiling) --------- #
        def inproc_worker(cix, r):
            mine = []
            for _ in range(per_client):
                q = ids[int(r.integers(n))]
                t1 = time.perf_counter()
                gw.closest_concepts("go", "transe", q, k=k)
                mine.append(time.perf_counter() - t1)
            return mine

        # best-of-2 (run.py's _time does the same): one bad descheduling
        # on a small CI box otherwise dominates the ratio
        wall, lat = min((fanout(inproc_worker) for _ in range(2)),
                        key=lambda x: x[0])
        inproc_qps = round(total / wall, 1)
        p50, p99 = _percentiles(lat)
        out["modes"].append({"mode": "gateway-inproc", "clients": clients,
                             "qps": inproc_qps, "p50_ms": p50, "p99_ms": p99,
                             "wall_s": round(wall, 3)})
        print(f"  http[inproc ] {clients:2d} clients x {per_client} calls: "
              f"{inproc_qps:>9,.0f} q/s  p50={p50:.3f}ms p99={p99:.3f}ms")

        # ---- mode 2: the same clients over real sockets --------------- #
        server = serve_http(gw, port=0)
        port = server.port

        def http_fleet():
            out = subprocess.run(
                [sys.executable, "-c", _CLIENT_DRIVER, str(port),
                 str(clients), str(per_client), str(n), str(k)],
                capture_output=True, text=True, timeout=600)
            assert out.returncode == 0, out.stderr[-2000:]
            rep = json.loads(out.stdout)
            return rep["wall"], rep["lat"]

        wall, lat = min((http_fleet() for _ in range(2)),
                        key=lambda x: x[0])
        http_qps = round(total / wall, 1)
        p50, p99 = _percentiles(lat)
        row = {"mode": "http", "clients": clients, "qps": http_qps,
               "p50_ms": p50, "p99_ms": p99, "wall_s": round(wall, 3),
               "vs_inproc": round(http_qps / inproc_qps, 2)}
        out["modes"].append(row)
        print(f"  http[socket ] {clients:2d} clients x {per_client} calls: "
              f"{http_qps:>9,.0f} q/s ({row['vs_inproc']:.2f}x in-process)  "
              f"p50={p50:.3f}ms p99={p99:.3f}ms")

        # ---- mode 3: conditional GET fast path (informational) -------- #
        n_cond = min(total, 256)
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        try:
            path = "/download/go/transe?version=2025-01&offset=0&limit=100"
            conn.request("GET", path)
            resp = conn.getresponse()
            etag = resp.getheader("ETag")
            resp.read()

            t0 = time.perf_counter()
            for _ in range(n_cond):
                conn.request("GET", path)
                resp = conn.getresponse()
                resp.read()
                assert resp.status == 200
            full_qps = round(n_cond / (time.perf_counter() - t0), 1)

            t0 = time.perf_counter()
            for _ in range(n_cond):
                conn.request("GET", path, headers={"If-None-Match": etag})
                resp = conn.getresponse()
                resp.read()
                assert resp.status == 304
            cond_qps = round(n_cond / (time.perf_counter() - t0), 1)
        finally:
            conn.close()
        out["modes"].append({"mode": "etag-304", "clients": 1,
                             "full_page_qps": full_qps,
                             "not_modified_qps": cond_qps,
                             "speedup": round(cond_qps / full_qps, 2)})
        print(f"  http[etag   ] 304 fast path: {cond_qps:>9,.0f} q/s vs "
              f"{full_qps:,.0f} q/s full pages "
              f"({cond_qps / full_qps:.1f}x)")

        server.close()
        gw.close()
        assert gw.scheduler.stats["resolved"] == gw.scheduler.stats["submitted"]

        out["http_vs_inproc"] = round(http_qps / inproc_qps, 2)
        out["floor"] = CI_FLOOR if fast else FLOOR
        out["pass"] = bool(out["http_vs_inproc"] >= out["floor"])
        return out


# --------------------------------------------------------------------- #
#                multi-process serving bench (--workers N)               #
# --------------------------------------------------------------------- #

def _pss_kb(pid: int):
    """Proportional set size of ``pid`` in kB — the honest per-process
    memory number: pages shared by M processes bill 1/M to each, so a
    pool over one mmap'd table sums to ~one table, not N. Returns
    (kb, basis); falls back to VmRSS where smaps_rollup is unavailable
    (RSS double-counts shared pages — callers skip the sublinearity
    assertion on that basis)."""
    try:
        for line in Path(f"/proc/{pid}/smaps_rollup").read_text().splitlines():
            if line.startswith("Pss:"):
                return int(line.split()[1]), "pss"
    except OSError:
        pass
    try:
        for line in Path(f"/proc/{pid}/status").read_text().splitlines():
            if line.startswith("VmRSS:"):
                return int(line.split()[1]), "rss"
    except OSError:
        pass
    return 0, "unavailable"


def _table_map_kb(pid: int, suffix: str = "/table.f32"):
    """Memory accounting for ``pid``'s mmap of the raw table file, from
    /proc/<pid>/smaps. Returns {rss_kb, pss_kb, private_kb, size_kb}
    summed over every ``table.f32`` mapping, or None where smaps is
    unavailable. A read-only file mapping shared across the pool shows
    private_kb ~ 0 and pool-summed pss_kb ~ one file; a copy-based
    design shows no such mapping at all (anon memory instead)."""
    try:
        text = Path(f"/proc/{pid}/smaps").read_text()
    except OSError:
        return None
    out = {"rss_kb": 0, "pss_kb": 0, "private_kb": 0, "size_kb": 0}
    active = False
    for line in text.splitlines():
        head = line[:1]
        if head.isdigit() or head in "abcdef":   # mapping header line
            active = line.rstrip().endswith(suffix)
        elif active:
            key, _, rest = line.partition(":")
            if key == "Rss":
                out["rss_kb"] += int(rest.split()[0])
            elif key == "Pss":
                out["pss_kb"] += int(rest.split()[0])
            elif key in ("Private_Dirty", "Private_Clean"):
                out["private_kb"] += int(rest.split()[0])
            elif key == "Size":
                out["size_kb"] += int(rest.split()[0])
    return out


def _publish_bench_registry(td: str, n: int, d: int) -> list:
    """Synthetic GO table published into a fresh registry (numpy only —
    this parent later talks to forked pools, so it must not run jax)."""
    from repro.core.registry import EmbeddingRegistry
    rng = np.random.default_rng(0)
    registry = EmbeddingRegistry(td)
    ids = [f"GO:{i:07d}" for i in range(n)]
    labels = [f"synthetic term {i}" for i in range(n)]
    emb = rng.standard_normal((n, d)).astype(np.float32)
    registry.publish("go", "2025-01", "transe", ids, labels, emb,
                     ontology_checksum="bench", hyperparameters={"dim": d})
    registry.seal("go", "2025-01")
    return ids


def _launch_pool(registry_root: str, workers: int):
    """Start ``python -m repro.api.workers`` and wait for its READY line.
    Returns (proc, port, worker_pids)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.api.workers",
         "--registry", registry_root, "--workers", str(workers),
         "--watch-interval-ms", "100"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        cwd=str(REPO))
    line = proc.stdout.readline().strip()
    if not line.startswith("READY"):
        err = proc.stderr.read()
        proc.kill()
        raise RuntimeError(f"worker pool failed to start: {line!r}\n{err}")
    port = int(line.split("port=")[1].split()[0])
    pids = [int(p) for p in line.split("pids=")[1].split()[0].split(",")]
    return proc, port, pids


def _stop_pool(proc) -> None:
    proc.send_signal(signal.SIGTERM)
    try:
        proc.communicate(timeout=30)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()


def _http_get_bytes(port: int, path: str) -> bytes:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        body = resp.read()
        assert resp.status == 200, body[:200]
        return body
    finally:
        conn.close()


def _fleet(port: int, clients: int, per_client: int, n: int, k: int):
    out = subprocess.run(
        [sys.executable, "-c", _CLIENT_DRIVER, str(port),
         str(clients), str(per_client), str(n), str(k)],
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rep = json.loads(out.stdout)
    return rep["wall"], rep["lat"]


def _publish_visible_s(registry_root: str, port: int, version: str,
                       n: int, d: int, timeout_s: float = 30.0) -> float:
    """Publish a new sealed version, then poll the pool's /versions until
    every route answer reflects it — the cross-process publish→visible
    latency (store watcher tick + invalidate + warm-build)."""
    from repro.core.registry import EmbeddingRegistry
    rng = np.random.default_rng(7)
    registry = EmbeddingRegistry(registry_root)
    ids = [f"GO:{i:07d}" for i in range(n)]
    labels = [f"synthetic term {i}" for i in range(n)]
    emb = rng.standard_normal((n, d)).astype(np.float32)
    registry.publish("go", version, "transe", ids, labels, emb,
                     ontology_checksum=f"bench-{version}",
                     hyperparameters={"dim": d})
    t0 = time.perf_counter()
    registry.seal("go", version)
    deadline = t0 + timeout_s
    while time.perf_counter() < deadline:
        body = json.loads(_http_get_bytes(port, "/versions/go"))
        if body.get("latest") == version:
            return round(time.perf_counter() - t0, 3)
        time.sleep(0.02)
    raise AssertionError(
        f"publish of {version} not visible after {timeout_s}s")


def _wire_parity(port: int, gw, ids, k: int) -> dict:
    """Byte-compare HTTP bodies from the pool against the in-process
    ``Gateway.handle`` wire dicts for a sample of every data route —
    the transport must add nothing and lose nothing."""
    from urllib.parse import parse_qsl, quote
    paths = [(f"/get-vector/go/transe?query={ids[i]}", None)
             for i in (0, 1, 7)]
    paths += [(f"/sim/go/transe?a={ids[2]}&b={ids[5]}", None),
              (f"/closest-concepts/go/transe?query={ids[3]}&k={k}", None),
              ("/download/go/transe?offset=0&limit=5", None),
              ("/autocomplete/go/transe"
               f"?prefix={quote('synthetic term 1')}&limit=5", None),
              ("/versions/go", None)]
    checked, mismatches = 0, []
    for path, _ in paths:
        body = _http_get_bytes(port, path)
        route, _, query = path.partition("?")
        payload = {}
        for key, value in parse_qsl(query):
            payload[key] = int(value) if value.isdigit() else value
        expect = json.dumps(gw.handle(route, payload)).encode("utf-8")
        checked += 1
        if body != expect:
            mismatches.append(path)
    return {"checked": checked, "mismatches": mismatches}


def run_mp(fast: bool = False, workers: int = 2, clients: int = 16,
           max_batch: int = 64, flush_after_ms: float = 2.0,
           total_requests: int | None = None) -> dict:
    """Multi-process vs single-process HTTP serving over the same
    mmap-backed store: q/s at ``clients`` concurrent connections, PSS
    sublinearity across the pool, publish→visible latency, and wire
    parity with the in-process gateway. Emits the BENCH_http_mp.json
    payload."""
    n = 2_000 if fast else 20_000
    d, k = 200, 10
    total = total_requests or (512 if fast else 2_048)
    per_client = max(1, total // clients)
    total = per_client * clients
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:
        cores = os.cpu_count() or 1

    out = {"n_classes": n, "dim": d, "k": k, "clients": clients,
           "workers": workers, "total_requests": total, "cpu_cores": cores,
           "table_bytes": n * d * 4, "modes": []}

    def timed_pool(n_workers: int):
        """(qps, p50, p99, pss_kb_per_worker, mem_basis, table_maps,
        visible_s)"""
        with tempfile.TemporaryDirectory() as td:
            _publish_bench_registry(td, n, d)
            proc, port, pids = _launch_pool(td, n_workers)
            try:
                wall, lat = min((_fleet(port, clients, per_client, n, k)
                                 for _ in range(2)), key=lambda x: x[0])
                mem = [_pss_kb(pid) for pid in pids]
                basis = mem[0][1] if mem else "unavailable"
                tmaps = [_table_map_kb(pid) for pid in pids]
                visible = _publish_visible_s(td, port, "2025-02", n, d)
            finally:
                _stop_pool(proc)
            p50, p99 = _percentiles(lat)
            return (round(total / wall, 1), p50, p99,
                    [m[0] for m in mem], basis, tmaps, visible)

    # ---- single-process baseline (same pool machinery, 1 worker) ------ #
    sp_qps, p50, p99, sp_mem, sp_basis, _sp_tmaps, sp_visible = timed_pool(1)
    out["modes"].append({"mode": "http-1worker", "clients": clients,
                         "qps": sp_qps, "p50_ms": p50, "p99_ms": p99,
                         "pss_kb": sp_mem, "publish_visible_s": sp_visible})
    print(f"  http[ 1 proc] {clients:2d} clients x {per_client} calls: "
          f"{sp_qps:>9,.0f} q/s  p50={p50:.3f}ms p99={p99:.3f}ms  "
          f"pss={sum(sp_mem)/1024:.0f}MB  publish->visible {sp_visible}s")

    # ---- the pool ----------------------------------------------------- #
    mp_qps, p50, p99, mp_mem, mp_basis, mp_tmaps, mp_visible = \
        timed_pool(workers)
    speedup = round(mp_qps / sp_qps, 2)
    out["modes"].append({"mode": f"http-{workers}worker", "clients": clients,
                         "qps": mp_qps, "p50_ms": p50, "p99_ms": p99,
                         "pss_kb": mp_mem, "publish_visible_s": mp_visible,
                         "vs_single": speedup})
    print(f"  http[{workers:2d} proc] {clients:2d} clients x {per_client} "
          f"calls: {mp_qps:>9,.0f} q/s ({speedup:.2f}x single)  "
          f"p50={p50:.3f}ms p99={p99:.3f}ms  "
          f"pss={sum(mp_mem)/1024:.0f}MB  publish->visible {mp_visible}s")

    # ---- memory: the table is shared pages, not copies ---------------- #
    # Gate on the table.f32 mapping itself (per-mapping smaps), not on
    # whole-process PSS: each worker carries ~125MB of private
    # XLA/interpreter footprint that drowns a 1.6MB CI-size table, so
    # the pool-vs-linear process ratio is pure noise at --fast. The
    # mapping-level numbers are exact at any size.
    maps = [m for m in mp_tmaps if m]
    mapped = [m for m in maps if m["rss_kb"] > 0]
    table_kb = max((m["size_kb"] for m in maps), default=0)
    pool_table_pss = sum(m["pss_kb"] for m in mapped)
    private_kb = sum(m["private_kb"] for m in mapped)
    mem_ok = None
    if maps:
        mem_ok = bool(
            mapped                        # served from a file mapping...
            and private_kb == 0           # ...with no COW'd copies...
            and pool_table_pss            # ...billed ~once pool-wide
            <= MP_TABLE_PSS_RATIO * table_kb + 64)
    out["memory"] = {
        "basis": mp_basis, "single_pss_kb": sum(sp_mem),
        "pool_pss_kb": sum(mp_mem),
        "linear_scaling_kb": workers * sum(sp_mem),
        "table_map_kb": table_kb,
        "table_mapped_workers": len(mapped),
        "table_pool_pss_kb": pool_table_pss,
        "table_private_kb": private_kb,
        "max_table_pss_ratio": MP_TABLE_PSS_RATIO,
        "table_shared": mem_ok}
    print(f"  http[memory ] pool PSS {sum(mp_mem)/1024:.0f}MB "
          f"(1-worker {sum(sp_mem)/1024:.0f}MB); table.f32 mapped by "
          f"{len(mapped)}/{len(mp_tmaps)} workers, pool PSS "
          f"{pool_table_pss}kB vs one file {table_kb}kB, "
          f"private {private_kb}kB -> "
          f"{'shared OK' if mem_ok else 'NOT SHARED' if mem_ok is False else 'smaps unavailable'}")

    # ---- wire parity vs the in-process gateway ------------------------ #
    # This parent runs jax now (index build for gw.handle) — AFTER every
    # fork above has already happened, so fork safety holds.
    from repro.api import Gateway
    from repro.core.registry import EmbeddingRegistry
    from repro.core.serving import ServingEngine
    with tempfile.TemporaryDirectory() as td:
        ids = _publish_bench_registry(td, n, d)
        proc, port, _pids = _launch_pool(td, min(workers, 2))
        try:
            gw = Gateway(ServingEngine(EmbeddingRegistry(td)))
            parity = _wire_parity(port, gw, ids, k)
            gw.close()
        finally:
            _stop_pool(proc)
    out["wire_parity"] = parity
    print(f"  http[parity ] {parity['checked']} routes byte-compared, "
          f"{len(parity['mismatches'])} mismatches")

    # ---- floor -------------------------------------------------------- #
    if cores >= workers + 1:
        floor, basis = (MP_CI_FLOOR, "ci") if fast else (MP_FLOOR, "full")
        speed_ok = speedup >= floor
    else:
        # time-slicing one core: no parallel speedup is physically
        # possible and the ratio is noise — record it, don't gate on it
        floor, basis = None, f"not gated ({cores} cores < " \
            f"{workers + 1} needed for parallel speedup)"
        speed_ok = True
    out["mp_vs_sp"] = speedup
    out["floor"] = floor
    out["floor_basis"] = basis
    out["publish_visible_delta_s"] = round(mp_visible - sp_visible, 3)
    out["pass"] = bool(
        speed_ok
        and not parity["mismatches"]
        and mem_ok is not False
        and mp_visible <= max(2.0, sp_visible + 1.0))
    return out


def write_results_mp(report: dict) -> Path:
    RESULTS.mkdir(parents=True, exist_ok=True)
    out = RESULTS / "BENCH_http_mp.json"
    merged = {}
    if out.exists():
        try:
            merged = json.loads(out.read_text())
        except json.JSONDecodeError:
            merged = {}
    merged.update(report)
    out.write_text(json.dumps(merged, indent=2))
    return out


def floor_speedup(report: dict) -> float:
    """The floor metric: HTTP q/s over in-process gateway q/s at the
    benchmark's client count."""
    return report.get("http_vs_inproc", 0.0)


def section_key(fast: bool) -> str:
    """Fast (CI-sized) runs record under their own key so they never
    overwrite a full-sized trajectory with smaller-n numbers."""
    return "http_fast" if fast else "http"


def write_results(report: dict) -> Path:
    RESULTS.mkdir(parents=True, exist_ok=True)
    out = RESULTS / "BENCH_http.json"
    merged = {}
    if out.exists():
        try:
            merged = json.loads(out.read_text())
        except json.JSONDecodeError:
            merged = {}
    merged.update(report)
    out.write_text(json.dumps(merged, indent=2))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="CI-sized table (2k classes instead of 20k)")
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--workers", type=int, default=None, metavar="N",
                    help="run the multi-process axis instead: N pre-forked "
                         "workers vs a 1-worker pool over the same "
                         "mmap-backed store; emits BENCH_http_mp.json")
    args = ap.parse_args()

    if args.workers is not None:
        rep = run_mp(fast=args.fast, workers=args.workers,
                     clients=args.clients)
        out = write_results_mp({section_key(args.fast): rep})
        print(f"[bench_http] wrote {out}")
        status = "PASS" if rep["pass"] else "FAIL"
        floor_txt = (f"floor {rep['floor']}x, " if rep["floor"] is not None
                     else "")
        print(f"[bench_http] {status}: {args.workers}-worker pool = "
              f"{rep['mp_vs_sp']:.2f}x single-process at "
              f"{rep['clients']} clients ({floor_txt}"
              f"{rep['floor_basis']}); table shared = "
              f"{rep['memory']['table_shared']}; "
              f"parity mismatches = "
              f"{len(rep['wire_parity']['mismatches'])}")
        if not rep["pass"]:
            sys.exit(1)
        return

    rep = run(fast=args.fast, clients=args.clients)
    out = write_results({section_key(args.fast): rep})
    print(f"[bench_http] wrote {out}")

    status = "PASS" if rep["pass"] else "FAIL"
    print(f"[bench_http] {status}: HTTP = {floor_speedup(rep):.2f}x the "
          f"in-process gateway at {rep['clients']} clients "
          f"(floor {rep['floor']}x)")
    if not rep["pass"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
