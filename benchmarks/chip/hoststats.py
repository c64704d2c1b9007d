"""What the host did while a window ran, so that a stalled host or a
starved client is never read as a slow server.

* ``cpu_counters``: CPU time the hypervisor gave to other machines
  (``steal`` in /proc/stat) and the time some task waited for a CPU
  (``some`` in /proc/pressure/cpu), read before and after a window;
* ``GcPauses``: the parent's garbage collections in the window;
* ``Heartbeat``: a thread that sleeps a few milliseconds at a time and
  records every wake-up that came much later than asked, in the parent
  and in each client process; a stall seen by all of them at once is a
  pause of the whole machine.

Nothing here imports JAX.
"""
from __future__ import annotations

import gc
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

_TICK_S = 1.0 / os.sysconf("SC_CLK_TCK")


def cpu_counters() -> Dict[str, Optional[float]]:
    """Seconds of CPU stolen (summed over CPUs) and of CPU pressure so
    far; None where the kernel does not say."""
    out: Dict[str, Optional[float]] = {"steal_s": None, "pressure_s": None}
    try:
        with open("/proc/stat") as f:
            fields = f.readline().split()
        if fields[0] == "cpu" and len(fields) > 8:
            out["steal_s"] = int(fields[8]) * _TICK_S
    except (OSError, ValueError, IndexError):
        pass
    try:
        with open("/proc/pressure/cpu") as f:
            for line in f:
                if line.startswith("some"):
                    total = line.split("total=")[1]
                    out["pressure_s"] = int(total) * 1e-6
    except (OSError, ValueError, IndexError):
        pass
    return out


def counter_delta(after: Dict[str, Optional[float]],
                  before: Dict[str, Optional[float]]
                  ) -> Dict[str, Optional[float]]:
    return {k: None if after[k] is None or before[k] is None
            else after[k] - before[k] for k in after}


class GcPauses:
    """Durations of this process's garbage collections while started."""

    def __init__(self):
        self.pauses: List[Tuple[int, float]] = []
        self._t = 0.0

    def _cb(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((int(info.get("generation", -1)),
                                time.perf_counter() - self._t))

    def __enter__(self) -> "GcPauses":
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._cb)

    def summary(self) -> Dict[str, float]:
        full = [d for g, d in self.pauses if g == 2]
        return {"collections": len(self.pauses), "full": len(full),
                "longest_ms": max((d for _, d in self.pauses),
                                  default=0.0) * 1e3}


class Heartbeat:
    """Wakes every ``period_s`` and records (monotonic start, seconds) of
    every gap longer than ``min_gap_s``: time in which no thread of this
    process ran."""

    def __init__(self, period_s: float = 0.002, min_gap_s: float = 0.02):
        self.period_s, self.min_gap_s = period_s, min_gap_s
        self.stalls: List[Tuple[float, float]] = []
        self._stop = threading.Event()
        self._th = threading.Thread(target=self._beat, daemon=True,
                                    name="chipbench-heartbeat")

    def _beat(self) -> None:
        last = time.monotonic()
        while not self._stop.is_set():
            time.sleep(self.period_s)
            now = time.monotonic()
            if now - last > self.min_gap_s:
                self.stalls.append((last, now - last))
            last = now

    def __enter__(self) -> "Heartbeat":
        self._th.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._th.join()


def describe(host: Dict[str, Optional[float]], gcs: Dict[str, float],
             stalls: Optional[List[Tuple[object, float, float]]] = None,
             t0: float = 0.0) -> str:
    """One line: the host's counters over the window, the parent's
    collections and the 16 longest stalls of the parent ("parent") and of
    the client processes (p0, p1, ...): seconds into the window and ms."""
    def ms(v):
        return "n/a" if v is None else f"{v * 1e3:.1f} ms"

    line = (f"host in the window: cpu steal {ms(host['steal_s'])}, cpu "
            f"pressure {ms(host['pressure_s'])}; parent gc "
            f"{gcs['collections']} collections ({gcs['full']} full), longest "
            f"{gcs['longest_ms']:.1f} ms")
    if stalls is None:
        return line
    longest = sorted(stalls, key=lambda s: -s[2])[:16]
    shown = ", ".join(
        f"{p if isinstance(p, str) else f'p{p}'}@{t - t0:+.2f}s "
        f"{g * 1e3:.0f} ms" for p, t, g in sorted(longest, key=lambda s: s[1]))
    return (f"{line}; stalls over 20 ms: {len(stalls)}"
            f"{' [' + shown + ']' if stalls else ''}")
