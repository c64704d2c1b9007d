"""Batch-analytics job benchmark (PR 9 tentpole).

Three measurements over one SCALE-profile snapshot (the GO-shaped
synthetic workload from ``configs/go_kge.py``, random embeddings — the
axis under test is the job subsystem, not training):

  * join parity — a bulk kNN join submitted through the job API must
    return the same neighbour ids, in the same order, as a serial
    per-query oracle driven straight at the index, with every score
    within ``SCORE_ATOL`` of the oracle's (a 256-row slab matmul and a
    one-row matmul may round the last float32 ulp differently). Gated at
    both sizes.
  * p99 under fire — interactive closest-concepts p99 from threaded
    clients while a full-table bulk join is RUNNING, vs the same probe
    quiescent. The executor yields between work slabs, so the ratio
    must stay within ``P99_RATIO`` at full size (recorded, not gated,
    at --fast: CI-sized kernels make single-request p99 noise-bound).
  * overflow fast-reject — with the job queue full, HTTP submissions
    must answer 429 + Retry-After in under ``REJECT_MEDIAN_MS`` median:
    admission control does no analytics work for a job it will not run.

Emits ``benchmarks/results/BENCH_jobs.json``.

Usage:
    PYTHONPATH=src python -m benchmarks.bench_jobs [--fast]
"""
from __future__ import annotations

import argparse
import http.client
import json
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "src"))

RESULTS = REPO / "benchmarks" / "results"
P99_RATIO = 2.0        # interactive p99 under a running bulk job
REJECT_MEDIAN_MS = 5.0  # HTTP 429 fast-reject median
SCORE_ATOL = 1e-6      # join vs serial oracle: float32 rounding of scores
K = 10


def _p(lat_s, q):
    return float(np.percentile(np.asarray(lat_s) * 1e3, q))


def _probe(gw, ids, requests, clients, rng):
    """Interactive latency probe: ``clients`` threads alternating sim /
    closest-concepts on (mostly unique) random queries; per-request
    wall-clock seconds, pooled."""
    picks = rng.integers(0, len(ids), (requests, 2))
    chunks = [list(range(c, requests, clients)) for c in range(clients)]
    lat, lock, errs = [], threading.Lock(), []

    def client(mine):
        out = []
        try:
            for i in mine:
                a, b = ids[int(picks[i][0])], ids[int(picks[i][1])]
                t0 = time.perf_counter()
                if i % 2:
                    gw.similarity("go-scale", "transe", a, b)
                else:
                    gw.closest_concepts("go-scale", "transe", a, k=K)
                out.append(time.perf_counter() - t0)
        except Exception as e:                     # pragma: no cover
            errs.append(e)
        with lock:
            lat.extend(out)

    threads = [threading.Thread(target=client, args=(c,)) for c in chunks]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errs:
        raise errs[0]
    return lat


def run(fast: bool = False) -> dict:
    from repro.api import Gateway
    from repro.configs.go_kge import SCALE
    from repro.core.registry import EmbeddingRegistry
    from repro.core.serving import ServingEngine
    from repro.ontology.synthetic import generate

    n = 2_000 if fast else 20_000
    d = 64 if fast else 128
    join_q = 256 if fast else 1024
    requests = 160 if fast else 400
    clients = 4
    rng = np.random.default_rng(0)

    out = {"n_classes": n, "dim": d, "k": K, "join_queries": join_q}

    with tempfile.TemporaryDirectory() as td:
        kg = generate(SCALE.spec, seed=0, n_terms=n)
        ids = list(kg.entities)
        registry = EmbeddingRegistry(td)
        emb = rng.standard_normal((n, d)).astype(np.float32)
        registry.publish("go-scale", "2025-01", "transe", ids,
                         [kg.terms[e].label for e in ids], emb,
                         ontology_checksum="bench",
                         hyperparameters={"dim": d})
        engine = ServingEngine(registry)
        gw = Gateway(engine, result_cache_entries=0, result_cache_bytes=0)

        # ---- 1. byte-identity: job join vs serial per-query oracle ---- #
        classes = [ids[int(i)] for i in rng.integers(0, n, join_q)]
        sub = gw.submit_job("knn-join", "go-scale", model="transe",
                            classes=classes, k=K)
        st = gw.job_wait(sub.job_id, timeout=600)
        assert st.state == "DONE", st.error
        rows, offset = [], 0
        while offset is not None:
            page = gw.job_result(sub.job_id, offset=offset, limit=1000)
            rows.extend(page.rows)
            offset = page.next_offset
        idx = engine._index("go-scale", "transe")
        t0 = time.perf_counter()
        oracle = [[c, [[cc.identifier, cc.score]
                       for cc in idx.top_k([c], k=K)[0]]] for c in classes]
        t_oracle = time.perf_counter() - t0
        identical = all(
            r[0] == o[0] and [n[0] for n in r[1]] == [n[0] for n in o[1]]
            and all(abs(a[1] - b[1]) <= SCORE_ATOL
                    for a, b in zip(r[1], o[1]))
            for r, o in zip(rows, oracle)) and len(rows) == len(oracle)
        out["join"] = {
            "matches_serial_oracle": bool(identical),
            "job_compute_s": st.summary["compute_s"],
            "serial_oracle_s": round(t_oracle, 4),
            "slabs": st.summary["slabs"],
        }
        print(f"  jobs[join] {join_q} queries over {n} rows: "
              f"matches oracle={identical} "
              f"(job {st.summary['compute_s']:.2f}s vs serial "
              f"{t_oracle:.2f}s, {st.summary['slabs']} slabs)")

        # ---- 2. interactive p99 while a bulk join runs ---------------- #
        _probe(gw, ids, 32, clients, rng)          # warm shapes + caches
        quiescent = _probe(gw, ids, requests, clients, rng)
        # a join big enough to outlast the probe (duplicates are fine:
        # one output row per input class)
        fire_classes = ids * (8 if fast else 2)
        sub = gw.submit_job("knn-join", "go-scale", model="transe",
                            classes=fire_classes, k=K)
        deadline = time.monotonic() + 60
        while gw.job_status(sub.job_id).state == "PENDING":
            assert time.monotonic() < deadline, "join never started"
            time.sleep(0.001)
        under_fire = _probe(gw, ids, requests, clients, rng)
        still_running = gw.job_status(sub.job_id).state == "RUNNING"
        gw.job_wait(sub.job_id, timeout=600)
        q99, f99 = _p(quiescent, 99), _p(under_fire, 99)
        ratio = f99 / q99 if q99 > 0 else float("inf")
        out["p99_under_fire"] = {
            "quiescent_p50_ms": round(_p(quiescent, 50), 3),
            "quiescent_p99_ms": round(q99, 3),
            "under_fire_p50_ms": round(_p(under_fire, 50), 3),
            "under_fire_p99_ms": round(f99, 3),
            "ratio": round(ratio, 2),
            "job_running_throughout": bool(still_running),
            "gated": not fast,
        }
        print(f"  jobs[p99] interactive p99 {q99:.2f}ms quiescent -> "
              f"{f99:.2f}ms under bulk join ({ratio:.2f}x, "
              f"job running throughout: {still_running})")

        # ---- 3. HTTP overflow fast-reject ----------------------------- #
        # a separate gateway whose executor is pinned down by a slow job
        # and whose queue holds exactly one more
        from repro.api import serve_http
        slow = Gateway(ServingEngine(registry), max_jobs_queued=1,
                       jobs_slab=64, jobs_yield_s=0.05)
        server = serve_http(slow, port=0)
        try:
            slow.submit_job("knn-join", "go-scale", model="transe",
                            classes=ids, k=K)      # occupies the executor
            deadline = time.monotonic() + 60
            while slow.jobs.stats()["running"] == 0:
                assert time.monotonic() < deadline
                time.sleep(0.001)
            slow.submit_job("knn-join", "go-scale", model="transe",
                            classes=ids[:64], k=K)  # fills the queue
            body = json.dumps({"kind": "knn-join", "ontology": "go-scale",
                               "model": "transe", "classes": ids[:8],
                               "k": K})
            conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                              timeout=30)
            rejects = []
            retry_after = None
            for _ in range(60):
                t0 = time.perf_counter()
                conn.request("POST", "/jobs/submit", body=body)
                resp = conn.getresponse()
                payload = resp.read()
                dt = time.perf_counter() - t0
                assert resp.status == 429, (resp.status, payload)
                retry_after = resp.getheader("Retry-After")
                rejects.append(dt)
            conn.close()
            med = _p(rejects, 50)
            out["overflow"] = {
                "rejects": len(rejects),
                "status": 429,
                "retry_after_header": retry_after,
                "reject_p50_ms": round(med, 3),
                "reject_p99_ms": round(_p(rejects, 99), 3),
            }
            print(f"  jobs[429] {len(rejects)} fast-rejects: median "
                  f"{med:.3f}ms (Retry-After: {retry_after})")
        finally:
            server.close()
            slow.close()
        gw.close()

        ok = (identical
              and retry_after is not None
              and med < REJECT_MEDIAN_MS
              and (fast or ratio <= P99_RATIO))
        out["p99_ratio_floor"] = P99_RATIO
        out["reject_median_floor_ms"] = REJECT_MEDIAN_MS
        out["pass"] = bool(ok)
        return out


def section_key(fast: bool) -> str:
    return "jobs_fast" if fast else "jobs"


def write_results(report: dict) -> Path:
    RESULTS.mkdir(parents=True, exist_ok=True)
    out = RESULTS / "BENCH_jobs.json"
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
                    help="CI-sized workload (2k classes; p99 ratio "
                         "recorded, not gated)")
    args = ap.parse_args()
    rep = run(fast=args.fast)
    out = write_results({section_key(args.fast): rep})
    print(f"[bench_jobs] wrote {out}")
    status = "PASS" if rep["pass"] else "FAIL"
    pf = rep["p99_under_fire"]
    print(f"[bench_jobs] {status}: join matches oracle="
          f"{rep['join']['matches_serial_oracle']}, "
          f"interactive p99 under fire = {pf['ratio']:.2f}x quiescent "
          f"({'gated' if pf['gated'] else 'recorded'}, "
          f"floor {P99_RATIO}x), 429 median "
          f"{rep['overflow']['reject_p50_ms']:.3f}ms "
          f"(floor {REJECT_MEDIAN_MS}ms)")
    if not rep["pass"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
