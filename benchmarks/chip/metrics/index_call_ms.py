"""Index: host time of the ``EmbeddingIndex.top_k_rows`` call inside a
top-k batch (queries, the kernel path, the fetch of the result and its
decoding), in ms per top-k batch in the window (``BatchScheduler.stats``
``topk_s``, the ``index.topk`` spans)."""


def read(rec):
    s = (rec.get("counters") or {}).get("scheduler") or {}
    batches = s.get("batches", 0) - s.get("sim_batches", 0)
    if s.get("topk_s") is None or batches <= 0:
        return None
    return 1e3 * s["topk_s"] / batches
