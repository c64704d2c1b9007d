#!/usr/bin/env python3
"""Chip smoke: the release -> serve path once, on a TPU, at GO width.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the sharded top-k only

One chip, in one process (the chip belongs to one process at a time):

  1. release  -- ``Updater.run_once`` trains the six KGE models on a
                 synthetic GO release at ``configs/go_kge.CONFIG`` width
                 (40,000 terms, d=200) for a few steps each, checks every
                 loss and every published table is finite, and publishes;
  2. serve    -- ``ServingEngine`` -> ``Gateway`` -> ``serve_http`` on an
                 ephemeral port answers get-vector, sim, closest-concepts
                 (k=10), a download page and autocomplete over real HTTP,
                 then a concurrent burst of closest-concepts requests that
                 the scheduler batches, then one knn-join job polled to DONE;
  3. oracle   -- closest-concepts, knn-join, sim and the vectors agree
                 with a host NumPy float64 brute force over the same raw
                 table and norms: neighbour ids equal except where scores
                 tie within ``SCORE_ATOL``, scores within ``SCORE_ATOL``;
  4. pallas   -- the same top-k through the Pallas kernel, compiled for
                 the chip (``tpu_custom_call`` in its lowering), agrees
                 with the oracle too.

``--chips 4`` runs only the sharded serving path that ``launch.serve``
takes on a multi-chip host: ``EmbeddingIndex(mesh=make_serving_mesh())``
over the GO-40k TransE table (random weights from the seed), with its
shards on four distinct devices, compared with the single-device index and
the oracle.

Data comes from ``--seed``; nothing is downloaded; the registry lives in a
temporary directory.  Lines starting ``[smoke]`` are progress, and the
times in them are not a benchmark.  The last line of standard output is
one JSON object naming the device.  Without a TPU the script exits
non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import http.client
import json
import sys
import tempfile
import threading
import time
import urllib.parse
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

N_TERMS = 40_000          # configs/go_kge.CONFIG
DIM = 200                 # the paper's dimension
K = 10                    # the paper's top-k
STEPS = 200               # training steps per model
BURST = 64                # concurrent closest-concepts requests
JOIN_CLASSES = 256        # knn-join job size
#: largest |served - float64 oracle| cosine score; a float32 dot over
#: d=200 with float32 products stays ~1e-6 away, a single bfloat16 pass
#: ~1e-3 away
SCORE_ATOL = 2e-5


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def require_tpu(chips: int):
    """The JAX devices, or exit non-zero: this script never falls back
    to another backend."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is "
                         f"{devices[0].platform!r}; chip_smoke.py runs only "
                         f"on a TPU")
    if len(devices) < chips:
        raise SystemExit(f"--chips {chips} needs {chips} TPU devices, "
                         f"JAX sees {len(devices)}")
    return devices


# ----------------------------- the oracle ------------------------------ #
class Oracle:
    """Host NumPy float64 brute-force cosine top-k over a raw table and
    its per-row norms (the same operands the served path reads)."""

    def __init__(self, ids, table: np.ndarray, norms: np.ndarray):
        self.ids = list(ids)
        self.row = {ident: r for r, ident in enumerate(self.ids)}
        t = np.asarray(table, np.float64)
        self.unit = t / np.maximum(np.asarray(norms, np.float64), 1e-12)[:, None]

    def scores(self, rows) -> np.ndarray:
        """(Q, N) float64 cosine scores with each query's own row at -inf."""
        rows = np.asarray(rows)
        s = self.unit[rows] @ self.unit.T
        s[np.arange(len(rows)), rows] = -np.inf
        return s

    def check(self, what: str, query_ids, hits, k: int) -> dict:
        """``hits[q]`` is the served ``[(identifier, score), ...]`` for
        ``query_ids[q]``.  Raises AssertionError on any disagreement;
        returns the agreement counts."""
        rows = [self.row[q] for q in query_ids]
        s = self.scores(rows)
        # stable sort: among equal scores the lower row wins, as served
        order = np.argsort(-s, axis=1, kind="stable")[:, :k]
        exact = total = 0
        max_err = 0.0
        for qi, (q, got) in enumerate(zip(query_ids, hits)):
            assert len(got) == min(k, len(self.ids) - 1), (what, q, len(got))
            got_rows = [self.row[ident] for ident, _ in got]
            assert len(set(got_rows)) == len(got_rows), (what, q, "dup")
            assert rows[qi] not in got_rows, (what, q, "self not excluded")
            for j, ((ident, score), r) in enumerate(zip(got, got_rows)):
                true = s[qi, r]
                want = s[qi, order[qi, j]]
                err = max(abs(score - true), abs(true - want))
                max_err = max(max_err, err)
                assert abs(score - true) <= SCORE_ATOL, (
                    what, q, j, ident, score, true)
                # a different id at rank j is a tie within the tolerance
                assert abs(true - want) <= SCORE_ATOL, (
                    what, q, j, ident, true, self.ids[order[qi, j]], want)
                exact += r == order[qi, j]
                total += 1
        log(f"oracle[{what}]: {len(hits)} queries, {exact}/{total} ids at "
            f"the oracle's rank, max |score - oracle| {max_err:.3e} "
            f"(tolerance {SCORE_ATOL:g})")
        return {"queries": len(hits), "exact": exact, "total": total,
                "max_err": max_err}


# ------------------------------ the phases ----------------------------- #
def release(root: str, n_terms: int, dim: int, steps: int, seed: int):
    """Train and publish one synthetic GO release; every loss and every
    published table must be finite.  Returns the registry."""
    from repro.configs.go_kge import CONFIG
    from repro.core.registry import EmbeddingRegistry
    from repro.core.updater import SyntheticReleaseChannel, Updater
    from repro.ontology.synthetic import generate

    t0 = time.perf_counter()
    kg = generate(CONFIG.spec, seed=seed, n_terms=n_terms)
    log(f"release: synthetic GO {kg.num_entities} terms, "
        f"{len(kg.triples)} triples, d={dim}, models {list(CONFIG.models)}")
    registry = EmbeddingRegistry(root)
    updater = Updater(registry, models=CONFIG.models, dim=dim,
                      train_cfg=CONFIG.train, steps_override=steps)
    rep = updater.run_once(SyntheticReleaseChannel("go", "2025-01", kg),
                           seed=seed)
    assert rep.changed and rep.trained_models == list(CONFIG.models), rep
    for m, d in rep.details.items():
        assert np.isfinite(d["final_loss"]), (m, d)
        _, _, table, norms, _ = registry.get_serving("go", m)
        assert table.shape == (n_terms, dim), (m, table.shape)
        assert np.isfinite(table).all() and np.isfinite(norms).all(), m
        log(f"release: {m:8s} {d['steps']} steps, loss "
            f"{d['final_loss']:.4f}, {d['triples_per_s']:.0f} triples/s "
            f"incl. compile (not a benchmark)")
    log(f"release: published {rep.version} in "
        f"{time.perf_counter() - t0:.1f}s")
    return registry


class _Client:
    """Real-socket HTTP client for one server; records wall time per
    route."""

    def __init__(self, port: int):
        self.port = port
        self.lat = {}
        self._lock = threading.Lock()

    def call(self, route: str, path: str, body=None):
        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=600)
        t0 = time.perf_counter()
        try:
            if body is None:
                conn.request("GET", path)
            else:
                conn.request("POST", path, json.dumps(body),
                             {"Content-Type": "application/json"})
            resp = conn.getresponse()
            data = resp.read()
        finally:
            conn.close()
        with self._lock:
            self.lat.setdefault(route, []).append(time.perf_counter() - t0)
        assert resp.status == 200, (path, resp.status, data[:500])
        return json.loads(data)


def serve(registry, seed: int, burst: int, join_classes: int) -> dict:
    """Stand up engine -> gateway -> HTTP, drive every route over real
    sockets and check the answers against the oracle.  Returns the
    query rows the burst used (for the Pallas phase) and the counts."""
    from repro.api import Gateway, serve_http
    from repro.checkpoint.store import norm_label
    from repro.core.serving import ServingEngine

    ids, labels, table, norms, _ = registry.get_serving("go", "transe")
    oracle = Oracle(ids, table, norms)
    rng = np.random.default_rng(seed)
    n = len(ids)
    engine = ServingEngine(registry, mesh=None)
    gw = Gateway(engine, flush_after_ms=5.0, max_batch=64)
    server = serve_http(gw, port=0)
    client = _Client(server.port)
    try:
        base = "/{}/go/transe"
        q = ids[int(rng.integers(n))]
        vec = client.call("get-vector", base.format("get-vector")
                          + f"?query={q}")
        assert np.array_equal(np.asarray(vec["vector"], np.float32),
                              table[oracle.row[q]]), "get-vector"
        a, b = (ids[int(i)] for i in rng.choice(n, 2, replace=False))
        sim = client.call("sim", base.format("sim") + f"?a={a}&b={b}")
        want = float(oracle.unit[oracle.row[a]] @ oracle.unit[oracle.row[b]])
        assert abs(sim["score"] - want) <= SCORE_ATOL, ("sim", sim, want)
        cc = client.call("closest-concepts", base.format("closest-concepts")
                         + f"?query={q}&k={K}")
        oracle.check("closest-concepts", [q],
                     [[(h["identifier"], h["score"]) for h in cc["results"]]],
                     K)
        page = client.call("download", base.format("download")
                           + "?offset=100&limit=50")
        assert page["total"] == n and len(page["rows"]) == 50
        for r, (ident, v) in enumerate(page["rows"], start=100):
            assert ident == ids[r] and np.array_equal(
                np.asarray(v, np.float32), table[r]), ("download", r)
        prefix = labels[int(rng.integers(n))][:6]
        ac = client.call("autocomplete", base.format("autocomplete")
                         + "?prefix=" + urllib.parse.quote(prefix))
        assert ac["completions"] and all(
            norm_label(c).startswith(norm_label(prefix))
            for c in ac["completions"]), ("autocomplete", prefix, ac)
        log(f"serve: get-vector, sim, closest-concepts k={K}, download, "
            f"autocomplete answered over HTTP on port {server.port}")

        # concurrent burst: distinct queries (no result-cache hits), all
        # released at once so the scheduler coalesces them
        rows = rng.choice(n, burst, replace=False)
        queries = [ids[int(r)] for r in rows]
        before = dict(gw.scheduler.stats)
        out = [None] * burst
        gate = threading.Barrier(burst)

        def one(i):
            gate.wait()
            out[i] = client.call("closest-concepts-burst",
                                 base.format("closest-concepts")
                                 + f"?query={queries[i]}&k={K}")

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(burst)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        batches = gw.scheduler.stats["batches"] - before["batches"]
        assert all(o is not None for o in out), "burst request lost"
        assert batches < burst, ("burst was not batched", batches)
        log(f"serve: burst of {burst} closest-concepts in {batches} "
            f"scheduler batches")
        burst_check = oracle.check(
            "burst", queries,
            [[(h["identifier"], h["score"]) for h in o["results"]]
             for o in out], K)

        # one knn-join job over the public job API, polled to DONE
        classes = [ids[int(r)] for r in rng.choice(n, join_classes,
                                                    replace=False)]
        sub = client.call("job-submit", "/jobs/submit",
                          {"kind": "knn-join", "ontology": "go",
                           "model": "transe", "classes": classes, "k": K})
        deadline = time.monotonic() + 600
        while True:
            st = client.call("job-status", f"/jobs/{sub['job_id']}")
            if st["state"] not in ("PENDING", "RUNNING"):
                break
            assert time.monotonic() < deadline, "knn-join job timed out"
            time.sleep(0.05)
        assert st["state"] == "DONE", st
        res = client.call("job-result", f"/jobs/{sub['job_id']}/result"
                          f"?limit={join_classes}")
        assert [r[0] for r in res["rows"]] == classes
        join_check = oracle.check(
            "knn-join", classes,
            [[(h[0], h[1]) for h in r[1]] for r in res["rows"]], K)
    finally:
        server.close()
        gw.close()
    for route, lat in sorted(client.lat.items()):
        log(f"latency[{route}]: n={len(lat)} median "
            f"{1e3 * float(np.median(lat)):.2f} ms (not a benchmark)")
    return {"rows": rows, "oracle": oracle, "burst": burst_check,
            "join": join_check, "batches": int(batches)}


def pallas_topk(registry, rows, oracle) -> dict:
    """The burst's queries again, through the Pallas kernel path of the
    same index type; must agree with the oracle."""
    from repro.core.serving import EmbeddingIndex

    ids, labels, table, norms, _ = registry.get_serving("go", "transe")
    idx = EmbeddingIndex(ids, labels, table, norms=norms, use_pallas=True)
    t0 = time.perf_counter()
    hits = idx.top_k_rows(rows, K)
    log(f"pallas: {len(rows)} queries in {time.perf_counter() - t0:.2f}s "
        f"incl. compile (not a benchmark)")
    return oracle.check("pallas", [ids[int(r)] for r in rows],
                        [[(c.identifier, c.score) for c in h] for h in hits],
                        K)


def pallas_lowering(q_rows: int, block_rows: int, dim: int) -> str:
    """The lowered text of the top-k kernel at one streamed slab's shape,
    as the serving path calls it."""
    import jax.numpy as jnp
    from repro.kernels.ops import STREAM_BLOCK_ROWS
    from repro.kernels.topk_similarity import topk_cosine_pallas

    n = min(block_rows, STREAM_BLOCK_ROWS)
    return topk_cosine_pallas.lower(
        jnp.zeros((q_rows, dim), jnp.float32), jnp.zeros((n, dim), jnp.float32),
        K, exclude_rows=jnp.zeros((q_rows,), jnp.int32),
        norms=jnp.ones((n,), jnp.float32), block_n=1024).as_text()


def run_single(root: str, n_terms: int = N_TERMS, dim: int = DIM,
               steps: int = STEPS, seed: int = 0, burst: int = BURST,
               join_classes: int = JOIN_CLASSES) -> dict:
    """Phases 1-4 on the default device; returns what each checked."""
    registry = release(root, n_terms, dim, steps, seed)
    served = serve(registry, seed, burst, join_classes)
    pallas = pallas_topk(registry, served["rows"], served["oracle"])
    return {"burst": served["burst"], "join": served["join"],
            "batches": served["batches"], "pallas": pallas}


def run_sharded(chips: int, n_terms: int = N_TERMS, dim: int = DIM,
                seed: int = 0, queries: int = BURST) -> dict:
    """The sharded serving top-k over the GO TransE table on a mesh of
    ``chips`` devices, against the single-device index and the oracle."""
    import jax
    from repro.configs.go_kge import CONFIG
    from repro.core.serving import EmbeddingIndex
    from repro.kge import make_model
    from repro.launch.mesh import make_serving_mesh
    from repro.ontology.synthetic import generate

    kg = generate(CONFIG.spec, seed=seed, n_terms=n_terms)
    model = make_model("transe", kg.num_entities, kg.num_relations, dim=dim)
    table = np.asarray(model.entity_embeddings(
        model.init(jax.random.key(seed))), np.float32)
    norms = np.linalg.norm(table, axis=1).astype(np.float32)
    ids = list(kg.entities)
    labels = [kg.label_of(e) for e in ids]
    mesh = make_serving_mesh()
    assert mesh is not None and mesh.devices.size == chips, mesh
    sharded = EmbeddingIndex(ids, labels, table, norms=norms, mesh=mesh)
    devices = {s.device for s in sharded._table_sharded.addressable_shards}
    assert len(devices) == chips, devices
    log(f"sharded: TransE table {table.shape} over {len(devices)} devices")
    solo = EmbeddingIndex(ids, labels, table, norms=norms)
    rows = np.random.default_rng(seed).choice(len(ids), queries,
                                              replace=False)
    t0 = time.perf_counter()
    got = sharded.top_k_rows(rows, K)
    log(f"sharded: {queries} queries in {time.perf_counter() - t0:.2f}s "
        f"incl. compile (not a benchmark)")
    ref = solo.top_k_rows(rows, K)
    same = sum(a.identifier == b.identifier
               for ga, gb in zip(got, ref) for a, b in zip(ga, gb))
    worst = max(abs(a.score - b.score)
                for ga, gb in zip(got, ref) for a, b in zip(ga, gb))
    assert worst <= SCORE_ATOL, ("sharded vs single device", worst)
    log(f"sharded vs single device: {same}/{queries * K} ids equal, max "
        f"|score difference| {worst:.3e}")
    oracle = Oracle(ids, table, norms)
    query_ids = [ids[int(r)] for r in rows]
    return {
        "sharded": oracle.check("sharded", query_ids,
                                [[(c.identifier, c.score) for c in h]
                                 for h in got], K),
        "single": oracle.check("single-device", query_ids,
                               [[(c.identifier, c.score) for c in h]
                                for h in ref], K),
    }


def _cache_entries(path: str) -> int:
    p = Path(path)
    return sum(1 for _ in p.iterdir()) if p.is_dir() else 0


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded serving top-k on a "
                         "four-chip mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    devices = require_tpu(args.chips)
    d0 = devices[0]
    log(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devices)}")
    cached = _cache_entries(cache_dir)
    t0 = time.perf_counter()
    if args.chips == 4:
        run_sharded(args.chips, seed=args.seed)
    else:
        text = pallas_lowering(BURST, N_TERMS, DIM)
        assert "tpu_custom_call" in text, "top-k kernel did not compile"
        log("pallas: tpu_custom_call in the top-k lowering (compiled, "
            "not interpreted)")
        with tempfile.TemporaryDirectory(prefix="chip-smoke-") as root:
            run_single(root, seed=args.seed)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s; compile "
        f"cache {cache_dir}: {cached} entries before, "
        f"{_cache_entries(cache_dir)} after")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
