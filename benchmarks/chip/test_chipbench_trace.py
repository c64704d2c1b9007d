"""The trace reduction, on hand-made events and on a small trace
recorded on a TPU v5e (``fixtures/trace_v5e_serve.json``)."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import tracing

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "trace_v5e_serve.json"
MS = 1_000_000  # ns


def _events():
    return {
        "devices": {
            "0": [["fusion.1", 1 * MS, 2 * MS],      # 1-3
                  ["copy.2", 2 * MS, 2 * MS],        # 2-4, overlaps
                  ["all-gather.3", 6 * MS, 1 * MS],  # 6-7
                  ["fusion.4", 9 * MS, 3 * MS]],     # 9-12, clipped at 10
            "1": [["fusion.1", 0, 4 * MS]],          # 0-4, clipped at 0.5
        },
        "host": [[tracing.WINDOW_SPAN, 0.5 * MS, 9.5 * MS],
                 ["gateway.handle", 4 * MS, 1.5 * MS],
                 ["outer", 0, 200 * MS],
                 ["$python", 4 * MS, 2 * MS],
                 ["TransferToDevice", 7.2 * MS, 1.6 * MS]],
    }


def test_busy_idle_compute_and_collectives():
    r = tracing.reduce(_events())
    assert r["window_s"] == pytest.approx(9.5e-3)
    # device 0 busy 1-4, 6-7, 9-10 = 5 ms; device 1 busy 0.5-4 = 3.5 ms
    assert r["busy_s"] == pytest.approx((5.0 + 3.5) / 2 * 1e-3)
    assert r["idle_share"] == pytest.approx(1 - 4.25 / 9.5)
    # not copies: device 0 has 2 + 1 + 1 ms, device 1 has 3.5 ms
    assert r["compute_s"] == pytest.approx((4.0 + 3.5) / 2 * 1e-3)
    assert r["collective_s"] == pytest.approx(0.5e-3)
    assert r["devices"] == 2
    names = [n for n, _ in r["device_ops"]]
    assert names[0] == "fusion.1"


def test_idle_gaps_are_charged_to_what_the_host_did():
    r = tracing.reduce(_events())
    gaps = dict(r["idle_gaps"])
    # device 0 is idle 0.5-1, 4-6 and 7-9 (ms)
    assert gaps["gateway.handle"] == pytest.approx(2e-3)
    assert gaps["TransferToDevice"] == pytest.approx(2e-3)
    assert gaps["idle"] == pytest.approx(0.5e-3)
    assert "outer" not in gaps and "$python" not in gaps


def test_no_window_or_no_device_work_reads_nothing():
    ev = _events()
    ev["host"] = ev["host"][1:]
    assert tracing.reduce(ev) is None
    ev = _events()
    ev["devices"] = {"0": [["fusion", 20 * MS, MS]]}
    assert tracing.reduce(ev) is None


def _naive(ev):
    """Busy time on a 1 us grid: an independent check of the union."""
    lo, hi = tracing._window(ev)
    out = []
    for dev, ops in ev["devices"].items():
        grid = np.zeros(int((hi - lo) // 1000) + 1, bool)
        for _, s, d in ops:
            a = max(s, lo)
            b = min(s + d, hi)
            if b > a:
                grid[int((a - lo) // 1000):int((b - lo) // 1000)] = True
        if grid.any():
            out.append(grid.sum() * 1e-6)
    return sum(out) / len(out)


def test_recorded_v5e_trace():
    ev = json.loads(FIXTURE.read_text())
    r = tracing.reduce(ev)
    assert r is not None and r["devices"] == 1
    assert 0.0 < r["busy_s"] <= r["window_s"]
    assert r["busy_s"] == pytest.approx(_naive(ev), rel=0.02, abs=2e-6)
    lo, hi = tracing._window(ev)
    compute = sum(min(s + d, hi) - max(s, lo)
                  for name, s, d in ev["devices"]["0"]
                  if min(s + d, hi) > max(s, lo)
                  and not name.split(" ")[0].lstrip("%").startswith("copy"))
    assert r["compute_s"] == pytest.approx(compute * 1e-9)
    # the step's fusions read copy-done operands and are compute all the same
    assert any("copy-done" in n and not n.startswith("%copy")
               for n, _, _ in ev["devices"]["0"])
    assert r["compute_s"] > 0.9 * r["busy_s"]
    assert r["collective_s"] == 0.0
    assert 0.0 <= r["idle_share"] < 1.0
