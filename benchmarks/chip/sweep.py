#!/usr/bin/env python3
"""Rate sweep of an open-loop serving cell, in one process on the chip.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
        --seconds <s> --rates 200,400,800

Sets the cell up once, then offers each rate for one window and prints
one JSON line per rate: client p50/p99, failures, how late the generator
ran, and whether completions kept pace with arrivals (the backlog: how
long after the last due time the last reply came, and the median
latency of the window's last fifth over its first fifth).  The knee is
the highest rate that kept pace with no failures and the generator on
time (``knee``); the last line gives it and four fifths of it, the
cell's fixed rate.  The sweep is not a run of the benchmark and prints
no result line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import client, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    cfg = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    fleet = client.Fleet(int(mix["client_processes"]),
                         int(mix["client_threads"]))
    try:
        from benchmarks.chip import run, serve_cell
        devices = run.open_chips(int(cell["chips"]))
        with serve_cell.deployment(cfg, args.seed, spans=False) as dep:
            dep.warm(serve_cell.traffic.warm_rows(
                mix, len(dep.ids), spec.subseed(args.seed, "traffic")))
            rows = []
            for i, rate in enumerate(float(r) for r in args.rates.split(",")):
                t = time.perf_counter()
                rec = serve_cell.run_window(dep, fleet, mix, rate,
                                            args.seconds, args.seed + i)
                rows.append({"rate": rate, **_pace(rec, args.seconds),
                             "wall_s": time.perf_counter() - t,
                             "device": devices[0].device_kind})
                print(json.dumps(rows[-1]), flush=True)
        k = knee(rows)
        print(json.dumps({"knee": k, "rate_per_s": None if k is None
                          else round(0.8 * k)}), flush=True)
    finally:
        fleet.close()
    return 0


def knee(rows) -> float:
    """The highest rate that kept pace: no failure, the generator on time
    (p99 under 5 ms), the queue drained within 0.25 s of the last due
    time, and a median at most twice the lightest rate's (a standing or
    growing queue fails it)."""
    light = min(rows, key=lambda r: r["rate"])["p50_ms"]
    ok = [r["rate"] for r in rows
          if r["failed"] == 0
          and r["gen_late_p99_ms"] < client.GEN_LATE_P99_LIMIT_MS
          and r["drain_after_last_due_s"] < 0.25
          and r["p50_ms"] <= 2.0 * light]
    return max(ok) if ok else None


def _pace(rec, seconds: float) -> dict:
    import numpy as np

    c = rec["client"]
    lat = np.asarray(rec["latency_ms"])
    fifth = max(1, len(lat) // 5)
    first = float(np.median(lat[:fifth]))
    last = float(np.median(lat[-fifth:]))
    s = rec["counters"]["scheduler"]
    batches = s["batches"] - s["sim_batches"]
    return {"requests": rec["requests"], "failed": c["failed"],
            "p50_ms": c["p50_ms"], "p99_ms": c["p99_ms"],
            "last_fifth_over_first": last / first if first > 0 else None,
            "drain_after_last_due_s": rec["drain_s"],
            "gen_late_p99_ms": c["gen_late_p99_ms"],
            "queries_per_batch": s["resolved"] / batches if batches else None,
            "hit_share": _hit(rec), "host": rec["host"]}


def _hit(rec):
    c = rec["counters"].get("result_cache") or {}
    n = c.get("hits", 0) + c.get("misses", 0)
    return c["hits"] / n if n else None


if __name__ == "__main__":
    sys.exit(main())
