"""Scheduler: mean wait of a top-k ticket from submit to the start of the
kernel batch that served it, in ms, over the window
(``BatchScheduler.stats`` deltas ``queue_wait_s`` / ``queued``)."""


def read(rec):
    s = (rec.get("counters") or {}).get("scheduler") or {}
    wait, queued = s.get("queue_wait_s"), s.get("queued")
    if wait is None or not queued:
        return None
    return 1e3 * wait / queued
