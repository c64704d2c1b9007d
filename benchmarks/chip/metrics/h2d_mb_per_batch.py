"""Index, host to device: MB of table slabs (rows and norms) streamed per
top-k batch in the window (``kernels.ops.stream_stats`` blocks; each slab
is padded to the block size).  0 where the table is resident on the
devices."""


def read(rec):
    s = (rec.get("counters") or {}).get("scheduler") or {}
    blocks = ((rec.get("counters") or {}).get("stream") or {}).get("blocks")
    batches = s.get("batches", 0) - s.get("sim_batches", 0)
    if blocks is None or batches <= 0:
        return None
    w = rec["work"]
    rows = min(w["block_rows"], w["n_rows"])
    return blocks * rows * (w["dim"] + 1) * 4 / 1e6 / batches
