"""Benchmark harness — one benchmark per paper claim/functionality.

The paper is a resource paper (no numeric tables), so each benchmark
corresponds to a system capability it claims:

  B1 kge-training     six KGE models, dim=200 (paper §3): triples/s each
  B2 serving          the three endpoints (paper §4, Fig. 1): download
                      build time, similarity latency, top-k latency —
                      numpy brute force (the paper's implementation) vs
                      jnp oracle vs fused Pallas kernel (interpret on CPU),
                      solo vs batched
  B3 update-pipeline  release->retrain->publish->invalidate wall time
                      across an evolving version series (paper §4 update
                      mechanism)
  B4 rdf2vec-walks    vectorized random-walk corpus rate (paper §3 RDF2Vec)
  B5 serving-sched    BatchScheduler queries/sec + p50 latency per padding
                      bucket vs per-request top_k (benchmarks/bench_serving.py);
                      also written standalone to results/BENCH_serving.json
                      so later PRs have a perf trajectory to beat
  B6 concurrent       flush-loop throughput + p50/p99 under 1/4/16 submitter
                      threads vs the synchronous single-caller baseline
                      (benchmarks/bench_concurrent.py; floor: 2x at 16
                      threads), written to results/BENCH_concurrent.json
  B7 update-warm      cold vs warm update pipeline over a low-churn release
                      series: delta policy + warm-start vs full retrain —
                      wall-clock speedup (floor: 2x mid-series) + link-
                      prediction MRR parity (benchmarks/bench_update.py),
                      written to results/BENCH_update.json
  B8 gateway          batched gateway vs direct per-call ServingEngine at
                      16 concurrent clients (floor: 2x), plus the async
                      front end vs threaded tickets (floor: 0.9x)
                      (benchmarks/bench_gateway.py), written to
                      results/BENCH_gateway.json
  B9 http             the stdlib HTTP service layer vs the in-process
                      gateway at 16 keep-alive clients (floor: 0.5x),
                      plus the ETag/304 conditional-GET fast path
                      (benchmarks/bench_http.py), written to
                      results/BENCH_http.json
  B10 http-mp         pre-forked multi-process serving over the shared
                      mmap store vs a 1-worker pool: q/s at 16 clients
                      (floor: 1.5x with enough cores), table.f32
                      page-sharing proof (smaps PSS), wire byte-parity,
                      cross-process publish->visible latency
                      (bench_http.py --workers), written to
                      results/BENCH_http_mp.json
  B11 cache           version-keyed result cache + admission control:
                      Zipf (s=1.1) mixed workload cache-on vs cache-off
                      q/s (floor: 5x full / 2x fast), byte identity
                      across the publish->invalidate edge, burst p99
                      of accepted <= 3x quiescent, fast-reject median
                      < 5ms, HTTP 429 + Retry-After spot check
                      (benchmarks/bench_cache.py), written to
                      results/BENCH_cache.json
  B12 scale           GO-scale serving curve: generate -> train -> publish
                      -> serve per rung N (10k/40k/100k; --fast 1k/4k/10k
                      in isolated subprocesses), q/s, publish->first-query,
                      index build, peak RSS; gates: streamed O(block)
                      device residency, per-row cost ratio <= 2x,
                      sub-linear q/s degradation
                      (benchmarks/bench_scale.py), written to
                      results/BENCH_scale.json
  B13 jobs            async batch-analytics jobs: bulk kNN join byte-
                      identical to the serial per-query oracle,
                      interactive p99 <= 2x quiescent while a bulk job
                      runs (gated full-size, recorded at --fast), and
                      queue-overflow 429 + Retry-After in < 5ms median
                      (benchmarks/bench_jobs.py), written to
                      results/BENCH_jobs.json
  B14 analysis        repo-native invariant analyzer (repro.analysis)
                      over src/: must finish in < 10 s with zero
                      unsuppressed findings; full report written to
                      results/ANALYSIS_report.json

Usage:
    PYTHONPATH=src python -m benchmarks.run                # full benchmarks
    PYTHONPATH=src python -m benchmarks.run --only X       # one section
    PYTHONPATH=src python -m benchmarks.run --only X --fast  # CI-sized X
    PYTHONPATH=src python -m benchmarks.run --fast         # repo smoke:
        the fast test tier (pytest -m "not slow") plus the 16-thread
        scheduler bench bucket — hot-path regressions caught in ~2 min
        instead of the full 5-minute suite.

Roofline tables come from the dry-run artifacts: see benchmarks/report.py.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "src"))
sys.path.insert(0, str(REPO))

RESULTS = REPO / "benchmarks" / "results"


def _time(fn, repeat=3):
    best = float("inf")
    out = None
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


# ===================================================================== #
def bench_kge_training(fast: bool) -> dict:
    import jax
    from repro.kge import make_model
    from repro.kge.train import KGETrainer, TrainConfig
    from repro.ontology.synthetic import GO_SPEC, generate

    n_terms = 400 if fast else 2000
    steps = 30 if fast else 100
    kg = generate(GO_SPEC, seed=0, n_terms=n_terms)
    cfg = TrainConfig(batch_size=512, num_negs=16, lr=1e-2)
    out = {}
    for name in ("transe", "transr", "distmult", "hole", "boxe", "rdf2vec"):
        if name == "rdf2vec":
            from repro.data import corpus, skipgram_pairs
            walks, vocab, pad = corpus(kg, jax.random.key(0),
                                       walks_per_entity=4, walk_length=4)
            pairs = skipgram_pairs(walks, window=2, pad_token=pad, seed=0)
            trips = np.stack([pairs[:, 0], np.zeros(len(pairs), np.int32),
                              pairs[:, 1]], axis=1)
            model = make_model(name, vocab, 1, dim=200)
        else:
            trips = kg.triples
            model = make_model(name, kg.num_entities, kg.num_relations,
                               dim=200)
        trainer = KGETrainer(model, cfg)
        _, _, stats = trainer.fit(trips, steps=steps)
        out[name] = {"triples_per_s": round(stats["triples_per_s"]),
                     "final_loss": round(stats["final_loss"], 4)}
        print(f"  B1 {name:10s} {stats['triples_per_s']:>12,.0f} triples/s "
              f"loss={stats['final_loss']:.4f}")
    return out


# ===================================================================== #
def bench_serving(fast: bool) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref

    n = 5_000 if fast else 40_000        # paper: GO > 40k classes
    d, k = 200, 10
    rng = np.random.default_rng(0)
    emb = rng.standard_normal((n, d)).astype(np.float32)
    unit = emb / np.linalg.norm(emb, axis=1, keepdims=True)
    ju = jnp.asarray(unit)

    out = {"n_classes": n}

    # --- the paper's implementation: numpy brute force, one query ------ #
    q1 = unit[:1]

    def numpy_topk():
        s = q1 @ unit.T
        idx = np.argpartition(-s[0], k)[:k]
        return idx[np.argsort(-s[0][idx])]
    t_np, _ = _time(numpy_topk)
    out["numpy_single_ms"] = round(t_np * 1e3, 3)

    # --- jnp oracle, single + batched ----------------------------------- #
    jq1 = jnp.asarray(q1)
    f_ref = jax.jit(lambda q: ref.topk_cosine_ref(q, ju, k))
    jax.block_until_ready(f_ref(jq1))
    t_ref, _ = _time(lambda: jax.block_until_ready(f_ref(jq1)))
    out["jnp_single_ms"] = round(t_ref * 1e3, 3)

    qb = jnp.asarray(unit[:64])
    f_ref_b = jax.jit(lambda q: ref.topk_cosine_ref(q, ju, k))
    jax.block_until_ready(f_ref_b(qb))
    t_ref_b, _ = _time(lambda: jax.block_until_ready(f_ref_b(qb)))
    out["jnp_batch64_ms"] = round(t_ref_b * 1e3, 3)
    out["jnp_batch64_per_query_ms"] = round(t_ref_b / 64 * 1e3, 4)

    # --- Pallas kernel in interpret mode (correctness proxy; compiled
    # path is TPU-only) ---------------------------------------------------#
    if not fast:
        from repro.kernels.topk_similarity import topk_cosine_pallas
        t_pl, _ = _time(lambda: jax.block_until_ready(
            topk_cosine_pallas(qb[:4], ju, k, interpret=True)), repeat=1)
        out["pallas_interpret_batch4_ms"] = round(t_pl * 1e3, 1)

    # --- similarity endpoint --------------------------------------------#
    t_sim, _ = _time(lambda: float(unit[3] @ unit[7]), repeat=10)
    out["similarity_ms"] = round(t_sim * 1e3, 5)

    # --- download payload ------------------------------------------------#
    ids = [f"GO:{i:07d}" for i in range(n)]
    t_dl, _ = _time(lambda: json.dumps(
        {i: [round(float(x), 6) for x in v]
         for i, v in zip(ids, emb[:1000])}), repeat=1)
    out["download_1000_classes_ms"] = round(t_dl * 1e3, 1)

    print(f"  B2 serving n={n}: numpy1={out['numpy_single_ms']}ms "
          f"jnp1={out['jnp_single_ms']}ms "
          f"jnp64={out['jnp_batch64_per_query_ms']}ms/q")
    return out


# ===================================================================== #
def bench_update_pipeline(fast: bool, tmpdir: Path) -> dict:
    from repro.core.registry import EmbeddingRegistry
    from repro.core.serving import ServingEngine
    from repro.core.updater import Updater
    from repro.kge.train import TrainConfig
    from repro.ontology.synthetic import GO_SPEC, release_series

    n_terms = 200 if fast else 800
    versions = 3 if fast else 6           # paper hosts six versions
    series = release_series(GO_SPEC, versions, seed=0, n_terms=n_terms)
    registry = EmbeddingRegistry(tmpdir / "bench_registry")
    engine = ServingEngine(registry)
    # B3 measures the paper's recompute-everything policy; churn_threshold=0
    # pins full retrains so its numbers stay comparable across PRs (the
    # warm-start path is benchmarked separately in B7 / bench_update.py)
    upd = Updater(registry, engine=engine, models=("transe", "distmult"),
                  dim=64, train_cfg=TrainConfig(batch_size=256, num_negs=8),
                  steps_override=40 if fast else 120, churn_threshold=0.0)

    out = {"versions": []}
    for tag, kg in series:
        class _Ch:
            name = "go"

            def latest(self, tag=tag, kg=kg):
                return tag, kg
        rep = upd.run_once(_Ch())
        out["versions"].append({"version": tag, "changed": rep.changed,
                                "wall_s": round(rep.wall_s, 2),
                                "n_entities": kg.num_entities})
        print(f"  B3 release {tag}: retrain+publish {rep.wall_s:.2f}s "
              f"({kg.num_entities} classes)")
    latest = registry.store.latest_version("go")
    assert latest == series[-1][0]
    out["served_latest"] = latest
    return out


# ===================================================================== #
def bench_walks(fast: bool) -> dict:
    import jax
    from repro.data import corpus
    from repro.ontology.synthetic import GO_SPEC, generate

    n = 1000 if fast else 5000
    kg = generate(GO_SPEC, seed=1, n_terms=n)

    def run():
        walks, vocab, pad = corpus(kg, jax.random.key(0),
                                   walks_per_entity=8, walk_length=4)
        jax.block_until_ready(walks)
        return walks
    t, _ = _time(run, repeat=1)
    n_walks = n * 8
    print(f"  B4 walks: {n_walks:,} walks of len 4 in {t:.2f}s "
          f"({n_walks/t:,.0f} walks/s)")
    return {"n_walks": n_walks, "wall_s": round(t, 3),
            "walks_per_s": round(n_walks / t)}


# ===================================================================== #
def bench_analysis() -> dict:
    """B14: the invariant analyzer must stay fast and the tree clean.

    Runs repro.analysis in-process over src/ against the committed
    baseline and writes the full report to results/ANALYSIS_report.json
    (the CI artifact). Pass = zero unsuppressed findings, no stale
    baseline entries, wall time under 10 s.
    """
    from repro.analysis import run_analysis

    budget_s = 10.0
    report = run_analysis([REPO / "src"], root=REPO,
                          baseline=REPO / "analysis_baseline.json")
    out = {
        "files": report.files,
        "findings": len(report.findings),
        "suppressed": len(report.suppressed),
        "baselined": len(report.baselined),
        "stale_baseline": len(report.stale_baseline),
        "elapsed_s": round(report.elapsed_s, 2),
        "budget_s": budget_s,
        "pass": report.ok and report.elapsed_s < budget_s,
    }
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / "ANALYSIS_report.json").write_text(
        json.dumps(report.to_json(), indent=2))
    print(f"  B14 analysis: {out['findings']} findings "
          f"({out['suppressed']} suppressed, {out['baselined']} baselined) "
          f"in {out['files']} files, {out['elapsed_s']}s "
          f"(budget {budget_s:.0f}s) -> "
          f"{'PASS' if out['pass'] else 'FAIL'}")
    return out


# ===================================================================== #
def run_smoke() -> int:
    """The repo smoke check: fast test tier + one scheduler bench bucket
    + a small cold-vs-warm update bucket.

    Catches hot-path (serving/scheduler/kernel) and update-pipeline
    regressions in ~2-3 minutes; the full suite and full benchmarks stay
    the tier-2 gate.
    """
    print("[smoke] fast test tier: pytest -m 'not slow'")
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + ":" + env.get("PYTHONPATH", "")
    t0 = time.perf_counter()
    tests = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-m", "not slow"],
        cwd=REPO, env=env)
    print(f"[smoke] tests done in {time.perf_counter() - t0:.0f}s "
          f"(exit {tests.returncode})")
    print("[smoke] scheduler bench bucket: 16-thread flush loop vs sync")
    from benchmarks.bench_concurrent import (FLOOR, floor_speedup,
                                             run as bench_conc_run,
                                             section_key, write_results)
    rep = bench_conc_run(fast=True, threads=(16,))
    write_results({section_key(True) + "_smoke": rep})
    s16 = floor_speedup(rep)
    print("[smoke] update bucket: CI-sized cold vs warm release series")
    from benchmarks import bench_update
    upd = bench_update.run(fast=True)
    bench_update.write_results(
        {bench_update.section_key(True) + "_smoke": upd})
    print("[smoke] gateway bucket: batched gateway vs direct per-call")
    from benchmarks import bench_gateway
    gwy = bench_gateway.run(fast=True)
    bench_gateway.write_results(
        {bench_gateway.section_key(True) + "_smoke": gwy})
    print("[smoke] cache bucket: Zipf result cache + admission control")
    from benchmarks import bench_cache
    cch = bench_cache.run(fast=True)
    bench_cache.write_results(
        {bench_cache.section_key(True) + "_smoke": cch})
    print("[smoke] jobs bucket: bulk join parity + 429 fast-reject")
    from benchmarks import bench_jobs
    jbs = bench_jobs.run(fast=True)
    bench_jobs.write_results(
        {bench_jobs.section_key(True) + "_smoke": jbs})
    print("[smoke] analysis bucket: invariant analyzer over src/")
    ana = bench_analysis()
    ok = (tests.returncode == 0 and s16 >= FLOOR and upd["pass"]
          and gwy["pass"] and cch["pass"] and jbs["pass"] and ana["pass"])
    print(f"[smoke] {'PASS' if ok else 'FAIL'}: tests "
          f"exit={tests.returncode}, 16-thread speedup={s16:.2f}x "
          f"(floor {FLOOR}x), warm update "
          f"{bench_update.floor_speedup(upd):.2f}x "
          f"(floor {upd['floor']}x, parity "
          f"{bench_update.quality_parity(upd)}), gateway "
          f"{bench_gateway.floor_speedup(gwy):.2f}x direct / async "
          f"{bench_gateway.async_ratio(gwy):.2f}x threaded "
          f"(floors {bench_gateway.FLOOR}x / {bench_gateway.ASYNC_RATIO}x), "
          f"cache {bench_cache.floor_speedup(cch):.2f}x "
          f"(floor {cch['floor']}x), jobs "
          f"{'PASS' if jbs['pass'] else 'FAIL'} "
          f"(429 median {jbs['overflow']['reject_p50_ms']:.3f}ms), "
          f"analysis {'PASS' if ana['pass'] else 'FAIL'} "
          f"({ana['findings']} findings, {ana['elapsed_s']}s)")
    return 0 if ok else 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="with --only: CI-sized inputs; alone: repo smoke "
                         "(fast test tier + one scheduler bench bucket)")
    ap.add_argument("--only", default=None,
                    choices=["kge", "serving", "update", "walks", "sched",
                             "concurrent", "gateway", "http", "http-mp",
                             "cache", "scale", "jobs", "analysis"])
    args = ap.parse_args()

    if args.fast and args.only is None:
        sys.exit(run_smoke())

    RESULTS.mkdir(parents=True, exist_ok=True)
    t0 = time.perf_counter()
    report = {}
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        # the sections whose child processes run JAX go first, while this
        # process has not started a backend: on an accelerator host the
        # parent would otherwise hold the chip the children need
        if args.only in (None, "scale"):
            print("[B12] GO-scale serving curve (subprocess rungs)")
            from benchmarks import bench_scale
            scl = bench_scale.run(fast=args.fast)
            bench_scale.write_results(
                {bench_scale.section_key(args.fast): scl})
            report["scale"] = scl
        if args.only in (None, "http-mp"):
            print("[B10] multi-process HTTP serving (pre-fork pool, "
                  "shared mmap store)")
            from benchmarks import bench_http
            mp_rep = bench_http.run_mp(fast=args.fast)
            bench_http.write_results_mp(
                {bench_http.section_key(args.fast): mp_rep})
            report["http_mp"] = mp_rep
        if args.only in (None, "kge"):
            print("[B1] KGE training throughput (six models, dim=200)")
            report["kge_training"] = bench_kge_training(args.fast)
        if args.only in (None, "serving"):
            print("[B2] serving endpoints")
            report["serving"] = bench_serving(args.fast)
        if args.only in (None, "update"):
            print("[B3] update pipeline (release series)")
            report["update_pipeline"] = bench_update_pipeline(
                args.fast, Path(td))
            print("[B7] delta-aware warm-start vs cold retrain")
            from benchmarks import bench_update
            upd_rep = bench_update.run(fast=args.fast)
            bench_update.write_results(
                {bench_update.section_key(args.fast): upd_rep})
            report["update_warm_start"] = upd_rep
        if args.only in (None, "walks"):
            print("[B4] RDF2Vec walk corpus")
            report["walks"] = bench_walks(args.fast)
        if args.only in (None, "sched"):
            print("[B5] serving scheduler throughput")
            from benchmarks.bench_serving import (run as bench_serving_run,
                                                  section_key, write_results)
            ref_report = bench_serving_run(fast=args.fast)
            write_results({section_key("ref", args.fast): ref_report})
            report["serving_scheduler"] = ref_report
        if args.only in (None, "concurrent"):
            print("[B6] concurrent flush-loop throughput")
            from benchmarks import bench_concurrent
            conc = bench_concurrent.run(fast=args.fast)
            bench_concurrent.write_results(
                {bench_concurrent.section_key(args.fast): conc})
            report["concurrent"] = conc
        if args.only in (None, "gateway"):
            print("[B8] gateway API throughput (batched vs direct, async)")
            from benchmarks import bench_gateway
            gwy = bench_gateway.run(fast=args.fast)
            bench_gateway.write_results(
                {bench_gateway.section_key(args.fast): gwy})
            report["gateway"] = gwy
        if args.only in (None, "http"):
            print("[B9] HTTP service layer throughput (socket vs in-process)")
            from benchmarks import bench_http
            htt = bench_http.run(fast=args.fast)
            bench_http.write_results(
                {bench_http.section_key(args.fast): htt})
            report["http"] = htt
        if args.only in (None, "cache"):
            print("[B11] result cache + admission control (Zipf s=1.1)")
            from benchmarks import bench_cache
            cch = bench_cache.run(fast=args.fast)
            bench_cache.write_results(
                {bench_cache.section_key(args.fast): cch})
            report["cache"] = cch
        if args.only in (None, "jobs"):
            print("[B13] async batch-analytics jobs (join parity, p99 "
                  "under fire, 429 fast-reject)")
            from benchmarks import bench_jobs
            jbs = bench_jobs.run(fast=args.fast)
            bench_jobs.write_results(
                {bench_jobs.section_key(args.fast): jbs})
            report["jobs"] = jbs
        if args.only in (None, "analysis"):
            print("[B14] invariant analyzer over src/")
            report["analysis"] = bench_analysis()

    report["total_wall_s"] = round(time.perf_counter() - t0, 1)
    out = RESULTS / ("bench_fast.json" if args.fast else "bench.json")
    out.write_text(json.dumps(report, indent=2))
    print(f"[bench] wrote {out} ({report['total_wall_s']}s total)")


if __name__ == "__main__":
    main()
