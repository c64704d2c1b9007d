#!/usr/bin/env python3
"""Readings that set a cell's check limits, in one process on the chip.

    python3 benchmarks/chip/controls.py --workload <cell> --seeds 1,2,3 \
        --seconds <s>

For every seed it prints one JSON line with the numbers ``correct``
compares, read three ways at the cell's own size:

* ``program``  the system under test, as a run drives it (serving: a
  short window at the cell's own rate, the same sample of answers;
  training: the checked first steps);
* ``control``  the reference in the program's place, one precision
  below the configuration's (serving: three bfloat16 passes per dot;
  training: every intermediate in bfloat16);
* ``half_batch`` (training) the reference with half of each batch left
  out and the mean taken over the rest.

A limit lies above the largest ``program`` reading and below the
smallest reading of the control (and, for training, of each fault that
reads ten times the program's).  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import client, spec  # noqa: E402


def serve_readings(cell, cfg, mix, fleet, seed: int, seconds: float):
    from benchmarks.chip import reference, serve_cell

    with serve_cell.deployment(cfg, seed, spans=False) as dep:
        dep.warm(serve_cell.traffic.warm_rows(
            mix, len(dep.ids), spec.subseed(seed, "traffic")))
        rec = serve_cell.run_window(dep, fleet, mix, float(cell["rate_per_s"]),
                                    seconds, seed)
    ref = reference.TopKReference(dep.table)
    rows = rec["rows"][rec["keep"]]
    program = reference.compare_topk(ref, rows, rec["answers"], dep.row_of,
                                     dep.labels, dep.k)
    program["failed"] = float(rec["client"]["failed"])
    ctl = reference.topk_control(dep.table, rows, dep.k, dep.ids, dep.labels)
    control = reference.compare_topk(ref, rows, ctl, dep.row_of, dep.labels,
                                     dep.k)
    return {"program": program, "control": control,
            "p99_ms": rec["client"]["p99_ms"]}


def train_readings(cfg, mix, model_ref, seed: int):
    from benchmarks.chip import checks, reference, train_cell

    tr = train_cell.Training(cfg, mix, seed)
    prog = tr.first_steps()
    tc = dict(cfg["train"])
    args = (tr.triples, tr.step_seeds, tr.n_entities)
    params0 = model_ref.init(tr.init_seed, tr.n_entities, tr.n_relations,
                             int(cfg["dim"]))
    tr.free()
    ref = model_ref.reference(params0, *args, tc)
    low = model_ref.reference(params0, *args, tc, rnd=reference.bfloat16)
    half = model_ref.reference(
        params0, *args, {**tc, "batch_size": tc["batch_size"] // 2})
    return {"program": checks.compare_training(prog, ref, params0),
            "control": checks.compare_training(low, ref, params0),
            "half_batch": checks.compare_training(half, ref, params0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    cfg = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    serving = mix["kind"] == "topk_http"
    model_ref = None if serving else spec.train_reference(cfg["model"])
    fleet = client.Fleet(int(mix["client_processes"]),
                         int(mix["client_threads"])) if serving else None
    try:
        from benchmarks.chip import run
        devices = run.open_chips(int(cell["chips"]))
        for seed in (int(s) for s in args.seeds.split(",")):
            out = (serve_readings(cell, cfg, mix, fleet, seed, args.seconds)
                   if serving else train_readings(cfg, mix, model_ref, seed))
            print(json.dumps({"cell": args.workload, "seed": seed, **out,
                              "device": devices[0].device_kind}), flush=True)
    finally:
        if fleet is not None:
            fleet.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
