"""Top-k kernel: roofline time of the work the traced batches required
over the device's compute time in the traced window, in %.  Work per
batch is 2*Q_pad*N*d flops and N*(d+1)*4 bytes (``work.topk_batches``);
compute time is every device operation that is not a copy, since the
serving device runs nothing else."""
from benchmarks.chip import work


def read(rec):
    trace = rec.get("trace")
    s = (rec.get("trace_counters") or {}).get("scheduler") or {}
    batches = s.get("batches", 0) - s.get("sim_batches", 0)
    if not trace or batches <= 0 or trace["compute_s"] <= 0:
        return None
    w = rec["work"]
    rows = s["resolved"] + s.get("padded_queries", 0)
    flops, nbytes = work.topk_batches(rows, batches, w["n_rows"], w["dim"])
    t, _ = work.roofline_s(flops, nbytes, rec["peaks"], rec["chips"])
    return 100.0 * t / trace["compute_s"]
