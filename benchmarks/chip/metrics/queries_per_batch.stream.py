"""Scheduler, where each batch streams the table: top-k tickets resolved
per top-k kernel batch in the window (``BatchScheduler.stats`` deltas;
similarity batches left out).  Each batch pays the whole stream, so the
median moves with it."""


def read(rec):
    s = (rec.get("counters") or {}).get("scheduler") or {}
    batches = s.get("batches", 0) - s.get("sim_batches", 0)
    return s["resolved"] / batches if batches > 0 else None
