"""The readers of the program's own spans and counters, on made-up
records, and the trace reduction charging idle time to a program span
nested inside the benchmark's ``gateway.handle``."""
from __future__ import annotations

import pytest

from benchmarks.chip import spec, tracing

MS = 1_000_000  # ns

#: counters of a window: 10 batches, 2 of them similarity batches
RECORD = {"counters": {
    "scheduler": {"batches": 10, "sim_batches": 2, "resolved": 40,
                  "queued": 32, "queue_wait_s": 0.64, "batch_s": 0.2,
                  "topk_s": 0.16},
    "stream": {"calls": 8, "blocks": 40, "stage_s": 0.04, "put_s": 0.08}}}

#: metric -> its value on RECORD, worked by hand
EXPECTED = {"queue_wait_ms": 640.0 / 32,
            "batch_ms": 200.0 / 8,
            "index_call_ms": 160.0 / 8,
            "slab_stage_ms_per_batch": 40.0 / 8,
            "slab_put_ms_per_batch": 80.0 / 8}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_span_reader_on_a_made_up_record(name):
    read = spec.metric_reader(name)
    assert read(RECORD) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_span_reader_without_the_new_keys_reads_nothing(name):
    """A record of a program without the spans, whose counters hold only
    the keys the parent's had, gives no value."""
    read = spec.metric_reader(name)
    old = {"counters": {
        "scheduler": {"batches": 10, "sim_batches": 2, "resolved": 40},
        "stream": {"calls": 8, "blocks": 40, "peak_block_bytes": 1}}}
    assert read(old) is None
    no_batch = {"counters": {
        "scheduler": {**RECORD["counters"]["scheduler"], "batches": 2,
                      "queued": 0},
        "stream": RECORD["counters"]["stream"]}}
    assert read(no_batch) is None


def test_span_metrics_are_declared_in_the_benchmark():
    bench = spec.benchmark()
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in EXPECTED:
        m = declared[name]
        assert m["unit"] == "ms" and m["better"] == "lower"
        assert m["moves"] == "query_p50_ms"
        assert all(spec.cell(bench, w) for w in m["workloads"])


def test_idle_gap_goes_to_the_program_span_inside_the_wrapper():
    """The benchmark's ``gateway.handle`` covers a whole request; a gap
    that a program span nested inside it covers goes to that span."""
    ev = {"devices": {"0": [["fusion.1", 1 * MS, 1 * MS],
                            ["fusion.2", 9 * MS, 1 * MS]]},
          "host": [[tracing.WINDOW_SPAN, 0, 10 * MS],
                   ["gateway.handle", 0.1 * MS, 9.8 * MS],
                   ["gateway.route", 0.2 * MS, 9.6 * MS],
                   ["scheduler.wait", 0.3 * MS, 9.3 * MS],
                   ["stream.put", 2.2 * MS, 5 * MS]]}
    gaps = dict(tracing.reduce(ev)["idle_gaps"])
    # idle 0-1 ms: the wrapper, the route and the wait each cover more
    # than half, the wait is the shortest; idle 2-9 ms: the slab put
    assert gaps["stream.put"] == pytest.approx(7e-3)
    assert gaps["scheduler.wait"] == pytest.approx(1e-3)
    assert "gateway.handle" not in gaps
