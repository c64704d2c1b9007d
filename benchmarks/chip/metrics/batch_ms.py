"""Scheduler: host time of one top-k batch, from resolving its names to
rows to its last ticket settled, in ms per top-k batch in the window
(``BatchScheduler.stats`` ``batch_s``, the ``scheduler.batch`` spans;
similarity batches left out)."""


def read(rec):
    s = (rec.get("counters") or {}).get("scheduler") or {}
    batches = s.get("batches", 0) - s.get("sim_batches", 0)
    if s.get("batch_s") is None or batches <= 0:
        return None
    return 1e3 * s["batch_s"] / batches
