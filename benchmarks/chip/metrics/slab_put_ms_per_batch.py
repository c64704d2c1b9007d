"""Index, host to device: host time spent handing staged slabs and their
norms to the device (``jnp.asarray``), in ms per top-k batch in the
window (``kernels.ops.stream_stats`` ``put_s``, the ``stream.put``
spans).  0 where the table is resident on the devices."""


def read(rec):
    c = rec.get("counters") or {}
    s = c.get("scheduler") or {}
    put = (c.get("stream") or {}).get("put_s")
    batches = s.get("batches", 0) - s.get("sim_batches", 0)
    if put is None or batches <= 0:
        return None
    return 1e3 * put / batches
