"""Index, host to device: host time spent copying table slabs into fresh
staging buffers, in ms per top-k batch in the window
(``kernels.ops.stream_stats`` ``stage_s``, the ``stream.stage`` spans).
0 where the table is resident on the devices."""


def read(rec):
    c = rec.get("counters") or {}
    s = c.get("scheduler") or {}
    stage = (c.get("stream") or {}).get("stage_s")
    batches = s.get("batches", 0) - s.get("sim_batches", 0)
    if stage is None or batches <= 0:
        return None
    return 1e3 * stage / batches
