"""Train step: device compute time (operations that are not copies) per
step in the traced stretch of steps, in ms."""


def read(rec):
    trace = rec.get("trace")
    steps = (rec.get("train") or {}).get("trace_steps")
    if not trace or not steps:
        return None
    return 1e3 * trace["compute_s"] / steps
