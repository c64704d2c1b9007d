"""Whole serve step: 2*Q_real*N*d flops of the queries that reached the
kernel in the window, over the window times the chips' bfloat16 peak,
in %."""


def read(rec):
    s = (rec.get("counters") or {}).get("scheduler") or {}
    q = s.get("resolved", 0)
    if q <= 0:
        return None
    w = rec["work"]
    flops = 2.0 * q * w["n_rows"] * w["dim"]
    peak = rec["peaks"]["bf16_flops_per_s"] * rec["chips"]
    return 100.0 * flops / (rec["seconds"] * peak)
