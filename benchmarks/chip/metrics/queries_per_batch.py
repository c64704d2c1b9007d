"""Scheduler: top-k tickets resolved per top-k kernel batch in the window
(``BatchScheduler.stats`` deltas; similarity batches left out).  Read in
the cell whose tail it moves; ``queries_per_batch.stream`` reads the same
count where each batch streams the table."""


def read(rec):
    s = (rec.get("counters") or {}).get("scheduler") or {}
    batches = s.get("batches", 0) - s.get("sim_batches", 0)
    return s["resolved"] / batches if batches > 0 else None
