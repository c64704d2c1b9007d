"""Gateway result cache: hits over lookups in the window, in %
(``ResultCache.stats`` deltas)."""


def read(rec):
    c = (rec.get("counters") or {}).get("result_cache") or {}
    n = c.get("hits", 0) + c.get("misses", 0)
    return 100.0 * c["hits"] / n if n else None
