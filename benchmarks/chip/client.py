"""Out-of-process HTTP client fleet for the open-loop serving cells.

The fleet's processes are spawned before the parent touches JAX and
never import it.  Each holds ``threads`` keep-alive connections; request
i of a window goes to process ``i % P`` and there to thread
``(i // P) % T``, which sleeps until the request is due, sends it and
reads the whole reply.  Latency is timed from the due time, so a stall
charges every request queued behind it.  Per request the fleet returns
its due and done times, how late it was sent and its HTTP status (-1:
no reply), the raw body of the requests picked for the check, and the
stalls of each client process (``hoststats.Heartbeat``).

A thread that is still waiting for a reply when its next request falls
due sends late; that lateness is the server's.  ``gen_late`` counts only
the lateness of a thread that was idle at the due time, so a starved
generator shows apart from a slow server.
"""
from __future__ import annotations

import http.client
import multiprocessing as mp
import queue
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from .hoststats import Heartbeat

#: the generator is on time while 99 % of its sends leave within this
#: many ms of their due time (the knee rule's limit, ``sweep.knee``)
GEN_LATE_P99_LIMIT_MS = 5.0


def _worker(proc: int, procs: int, threads: int, inbox, outbox) -> None:
    while True:
        job = inbox.get()
        if job is None:
            return
        outbox.put(_run_job(job, proc, procs, threads))


def _run_job(job: Dict[str, Any], proc: int, procs: int,
             threads: int) -> Dict[str, Any]:
    idx = np.arange(proc, len(job["offsets"]), procs)
    keep = set(job["keep"].tolist())
    n = len(idx)
    done = np.zeros(n)
    gen_late = np.zeros(n)
    status = np.full(n, -1, np.int32)
    bodies: Dict[int, bytes] = {}
    lock = threading.Lock()
    t0, port, timeout = job["t0"], job["port"], job["timeout_s"]

    def one(t: int) -> None:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
        conn.connect()
        prev_done = 0.0
        for j in range(t, n, threads):
            i = int(idx[j])
            due = t0 + float(job["offsets"][i])
            now = time.monotonic()
            if due > now:
                time.sleep(due - now)
            s = time.monotonic()
            gen_late[j] = max(0.0, s - max(due, prev_done))
            try:
                conn.request("GET", job["paths"][i])
                resp = conn.getresponse()
                body = resp.read()
                status[j] = resp.status
            except (OSError, http.client.HTTPException):
                body = b""
                conn.close()
                conn = http.client.HTTPConnection("127.0.0.1", port,
                                                  timeout=timeout)
            done[j] = prev_done = time.monotonic()
            if i in keep:
                with lock:
                    bodies[i] = body
        conn.close()

    pool = [threading.Thread(target=one, args=(t,), daemon=True)
            for t in range(threads)]
    with Heartbeat() as beat:
        for th in pool:
            th.start()
        for th in pool:
            th.join()
    return {"index": idx, "done": done, "gen_late": gen_late,
            "status": status, "bodies": bodies,
            "stalls": [(proc, t, g) for t, g in beat.stalls]}


class Fleet:
    """``procs`` spawned client processes of ``threads`` connections
    each, reused for every window of one parent process."""

    def __init__(self, procs: int, threads: int):
        ctx = mp.get_context("spawn")
        self.procs = procs
        self._outbox = ctx.Queue()
        self._inboxes = [ctx.Queue() for _ in range(procs)]
        self._workers = [
            ctx.Process(target=_worker, args=(p, procs, threads,
                                              self._inboxes[p], self._outbox),
                        daemon=True, name=f"chipbench-client-{p}")
            for p in range(procs)]
        for w in self._workers:
            w.start()

    def run(self, port: int, t0: float, paths: List[str],
            offsets: np.ndarray, keep: np.ndarray,
            wait_s: float, timeout_s: float = 120.0) -> Dict[str, Any]:
        """Send one window (due times ``t0 + offsets``, monotonic clock)
        and gather every reply; raises TimeoutError past ``wait_s``."""
        job = {"port": port, "t0": t0, "paths": paths,
               "offsets": np.asarray(offsets, np.float64),
               "keep": np.asarray(keep, np.int64), "timeout_s": timeout_s}
        for box in self._inboxes:
            box.put(job)
        parts = []
        deadline = time.monotonic() + wait_s
        while len(parts) < self.procs:
            left = deadline - time.monotonic()
            if left <= 0:
                raise TimeoutError("client fleet did not finish the window")
            try:
                parts.append(self._outbox.get(timeout=min(left, 1.0)))
            except queue.Empty:
                dead = [w.name for w in self._workers if not w.is_alive()]
                if dead:
                    raise RuntimeError(f"client processes died: {dead}")
        n = len(offsets)
        out = {"due": t0 + np.asarray(offsets, np.float64),
               "done": np.zeros(n),
               "gen_late": np.zeros(n), "status": np.full(n, -1, np.int32),
               "bodies": {}, "stalls": []}
        for part in parts:
            ix = part["index"]
            for key in ("done", "gen_late", "status"):
                out[key][ix] = part[key]
            out["bodies"].update(part["bodies"])
            out["stalls"].extend(part["stalls"])
        return out

    def close(self, timeout: float = 10.0) -> None:
        for box in self._inboxes:
            box.put(None)
        for w in self._workers:
            w.join(timeout)
        for w in self._workers:
            if w.is_alive():
                w.terminate()
                w.join(timeout)


def latency_summary(win: Dict[str, Any]) -> Dict[str, Optional[float]]:
    """Client-side latency in ms from the due time, failures at +inf."""
    ok = win["status"] == 200
    lat = np.where(ok, win["done"] - win["due"], np.inf) * 1e3
    return {"n": int(len(lat)), "failed": int((~ok).sum()),
            "p50_ms": percentile(lat, 50), "p99_ms": percentile(lat, 99),
            "gen_late_p99_ms": percentile(win["gen_late"] * 1e3, 99),
            "gen_late_max_ms": float(np.max(win["gen_late"]) * 1e3)
            if len(lat) else None}


def percentile(x, q: float) -> Optional[float]:
    """Linear-interpolated percentile (numpy's default) of raw samples;
    an infinite sample (a failed request) is larger than every finite
    one, so a tail that reaches the failures reads +inf."""
    x = np.sort(np.asarray(x, np.float64))
    if len(x) == 0:
        return None
    pos = (len(x) - 1) * q / 100.0
    lo = int(np.floor(pos))
    hi = min(lo + 1, len(x) - 1)
    if not np.isfinite(x[hi]):
        return float(x[hi]) if pos > lo else float(x[lo])
    return float(x[lo] + (x[hi] - x[lo]) * (pos - lo))
