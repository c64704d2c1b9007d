#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell's entry in BENCHMARK.json names its configuration, traffic mix
and chips; ``benchmarks/chip/workloads/<cell>.json`` gives its fixed rate
and the limits of its check.  With ``--trace 0`` the result carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics,
read from a profiler trace and the program's counters.  The last line of
standard output is one JSON object; the compared numbers and their
limits are the last lines on standard error.  Without a TPU, or with
fewer chips than the cell asks for, the run exits non-zero and prints no
result.

An open-loop cell spawns its HTTP client fleet before this process
touches JAX, so the clients never hold the chip.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import checks, client, hoststats, spec  # noqa: E402

#: JAX's persistent compilation cache: a fixed directory in the checkout
CACHE_DIR = ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(f"[chipbench] {time.perf_counter() - T_START:8.3f}s {msg}",
          file=sys.stderr, flush=True)


def open_chips(chips: int, platform: str = "tpu"):
    """The first ``chips`` JAX devices, or exit non-zero: no other backend
    stands in.  On the chip, JAX's compile cache is the checkout's fixed
    ``.jax_cache``; a rehearsal off the chip leaves JAX's settings alone."""
    if platform == "tpu":
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != platform:
        raise SystemExit(f"no {platform.upper()}: JAX's first device is "
                         f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices[:chips]


def memory_peak(devices) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks)


class CompileCounter:
    """Traces, compiles and compile-cache loads JAX reports, so a window
    can show that nothing compiled inside it."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/compilation_cache/cache_retrieval_time_sec": "cache_loads"}

    def __init__(self):
        import jax.monitoring

        self.counts = {v: 0 for v in self.EVENTS.values()}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        key = self.EVENTS.get(event)
        if key:
            self.counts[key] += 1

    def snapshot(self):
        return dict(self.counts)


def read_metrics(entries, record):
    out = {}
    for m in entries:
        value = spec.metric_reader(m["name"])(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def _reduce_trace(trace_dir):
    """Device numbers of a finished trace (None without one)."""
    if not trace_dir:
        return None
    from benchmarks.chip import tracing
    return tracing.reduce(tracing.extract(tracing.find_xplane(trace_dir)))


def serve(args, cell, cfg, mix, fleet, devices, peaks, compiles):
    from benchmarks.chip import reference, serve_cell
    from repro.kernels import ops as kops

    with serve_cell.deployment(cfg, args.seed, spans=bool(args.trace)) as dep:
        log("set-up: table published, server up")
        dep.warm(serve_cell.traffic.warm_rows(
            mix, len(dep.ids), spec.subseed(args.seed, "traffic")))
        setup_s = time.perf_counter() - T_START
        log("set-up: buckets compiled, result cache warmed")
        before = compiles.snapshot()
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
            if args.trace else None
        try:
            rec = serve_cell.run_window(dep, fleet, mix,
                                        float(cell["rate_per_s"]),
                                        args.seconds, args.seed, trace_dir)
            trace = _reduce_trace(trace_dir)
        finally:
            if trace_dir:
                shutil.rmtree(trace_dir, ignore_errors=True)
        in_window = {k: v - before[k] for k, v in compiles.snapshot().items()}
        mem = memory_peak(devices)
        table, ids, labels, row_of, k = (dep.table, dep.ids, dep.labels,
                                         dep.row_of, dep.k)
    c = rec["client"]
    on_time = c["gen_late_p99_ms"] < client.GEN_LATE_P99_LIMIT_MS
    log(f"window: {rec['requests']} requests at {cell['rate_per_s']}/s, "
        f"{c['failed']} failed; generator lateness p99 "
        f"{c['gen_late_p99_ms']:.3f} ms (limit "
        f"{client.GEN_LATE_P99_LIMIT_MS} ms), max "
        f"{c['gen_late_max_ms']:.3f} ms: "
        + ("on time" if on_time else "LATE, so the latencies of this window "
           "include stalls of the client fleet"))
    log(rec["host"])
    log(f"compilations inside the window: {in_window}")
    query_rows = rec["rows"][rec["keep"]]
    numbers = reference.compare_topk(reference.TopKReference(table),
                                     query_rows, rec["answers"], row_of,
                                     labels, k)
    numbers["failed"] = float(c["failed"])
    log(f"reference: {len(query_rows)} answers compared")
    record = {"cell": cell, "config": cfg, "peaks": peaks,
              "chips": len(devices), "seconds": args.seconds, **rec,
              "trace": trace, "setup_s": setup_s,
              "work": {"n_rows": len(ids), "dim": int(cfg["dim"]),
                       "block_rows": int(kops.STREAM_BLOCK_ROWS)},
              "generator": {"late_p99_ms": c["gen_late_p99_ms"],
                            "late_max_ms": c["gen_late_max_ms"],
                            "limit_p99_ms": client.GEN_LATE_P99_LIMIT_MS,
                            "on_time": bool(on_time)}}
    e2e = {"query_p50_ms": c["p50_ms"], "query_p99_ms": c["p99_ms"]}
    return record, e2e, numbers, mem, rec["requests"], c["failed"]


def train(args, cell, cfg, mix, model_ref, devices, peaks, compiles):
    from benchmarks.chip import train_cell

    tr = train_cell.Training(cfg, mix, args.seed)
    log("set-up: ontology, trainer and state built")
    prog = tr.first_steps()
    setup_s = time.perf_counter() - T_START
    log("set-up: checked steps done")
    before = compiles.snapshot()
    host0 = hoststats.cpu_counters()
    with hoststats.GcPauses() as gcs, hoststats.Heartbeat() as beat:
        t0 = time.monotonic()
        win = train_cell.window(tr, args.seconds, args.seed)
    host = hoststats.counter_delta(hoststats.cpu_counters(), host0)
    in_window = {k: v - before[k] for k, v in compiles.snapshot().items()}
    trace = tsteps = None
    if args.trace:
        with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as d:
            tsteps = train_cell.traced(tr, float(mix["trace_seconds"]),
                                       args.seed, d)["steps"]
            trace = _reduce_trace(d)
    mem = memory_peak(devices)
    triples, n_ent, n_rel = tr.triples, tr.n_entities, tr.n_relations
    step_seeds, init_seed = tr.step_seeds, tr.init_seed
    tr.free()
    log(f"window: {win['steps']} steps, {win['triples']} triples in "
        f"{win['elapsed_s']:.3f} s")
    log(hoststats.describe(host, gcs.summary(),
                           [("parent", t, g) for t, g in beat.stalls], t0))
    log(f"compilations inside the window: {in_window}")
    tc, dim = cfg["train"], int(cfg["dim"])
    params0 = model_ref.init(init_seed, n_ent, n_rel, dim)
    ref = model_ref.reference(params0, triples, step_seeds, n_ent, tc)
    numbers = checks.compare_training(prog, ref, params0)
    log("reference: checked steps followed")
    rate = win["triples"] / win["elapsed_s"]
    record = {"cell": cell, "config": cfg, "peaks": peaks,
              "chips": len(devices), "seconds": args.seconds,
              "train": {**win, "triples_per_s": rate, "trace_steps": tsteps},
              "trace": trace, "setup_s": setup_s,
              "work": {"model": cfg["model"], "flops_per_triple":
                       model_ref.flops_per_triple(dim, int(tc["num_negs"]))}}
    return record, {"train_triples_per_s": rate}, numbers, mem, \
        win["steps"], 0


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run(args: argparse.Namespace, platform: str = "tpu") -> dict:
    """One run of one cell; returns the result line's object.  Only a
    rehearsal off the chip names another ``platform``."""
    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    cfg = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    model_ref = (spec.train_reference(cfg["model"])
                 if mix["kind"] == "kge_train" else None)
    fleet = None
    if mix["kind"] == "topk_http":
        fleet = client.Fleet(int(mix["client_processes"]),
                             int(mix["client_threads"]))
    try:
        devices = open_chips(int(cell["chips"]), platform)
        d0 = devices[0]
        log("set-up: JAX sees the chips")
        peaks = spec.peaks(d0.device_kind)
        compiles = CompileCounter()
        log(f"device: {d0.platform} {d0.device_kind} x{len(devices)}")
        if mix["kind"] == "topk_http":
            out = serve(args, cell, cfg, mix, fleet, devices, peaks,
                        compiles)
        elif mix["kind"] == "kge_train":
            out = train(args, cell, cfg, mix, model_ref, devices, peaks,
                        compiles)
        else:
            raise spec.SpecError(f"unknown traffic kind {mix['kind']!r}")
    finally:
        if fleet is not None:
            fleet.close()
    record, e2e, numbers, mem, attempted, failed = out
    e2e["setup_s"] = record["setup_s"]
    verdict = checks.judge(numbers, cell["limits"])
    if args.trace:
        metrics = read_metrics(spec.per_layer(bench, args.workload), record)
    else:
        metrics = {}
        for m in spec.end_to_end(bench, args.workload):
            value = e2e.get(m["name"])
            if value is not None and math.isfinite(value):
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": verdict["correct"], "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    trace = record.get("trace")
    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    if "generator" in record:
        result["generator"] = record["generator"]
    result["checks"] = verdict["checks"]
    return result


def main(argv=None) -> int:
    result = run(parse(argv))
    checks.print_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
