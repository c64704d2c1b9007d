"""Sharded merge: device time of the collectives (the all-gather of each
shard's candidates) per top-k batch in the traced window, in ms, mean
over the chips.  Nothing to read on one chip."""


def read(rec):
    trace = rec.get("trace")
    s = (rec.get("trace_counters") or {}).get("scheduler") or {}
    batches = s.get("batches", 0) - s.get("sim_batches", 0)
    if not trace or batches <= 0 or trace["collective_s"] <= 0:
        return None
    return 1e3 * trace["collective_s"] / batches
