"""Top-k from a table resident on one device (``kernels.ops.ResidentTable``).

The resident launch runs the streamed path's slab step in a ``fori_loop``
over the device copy, so on the same table it must return the same ids
and bit-identical scores as streaming.  ``ServingEngine`` keeps a table
resident only when the device reports memory stats and the table fits
half of them over the LRU's capacity; the tests pick a regime by
monkeypatching ``kops.device_bytes_limit`` (the CPU backend reports
none, so it streams).  Fast tier — tiny tables on the CPU.
"""
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.serving import (BatchScheduler, EmbeddingIndex,
                                ServingEngine, TopKRequest)
from repro.kernels import ops as kops
from repro.kernels import ref

#: 45 rows over 16-row slabs: three slabs, the last padded by three rows
N, D, SLAB = 45, 12, 16


def _table(seed=7, n=N):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((n, D)) * 3.0).astype(np.float32)


def _streamed(table, rows, k, exclude_self):
    norms = np.linalg.norm(table, axis=1).astype(np.float32)
    q = table[rows] / np.maximum(norms[rows][:, None], 1e-12)
    excl = rows if exclude_self else np.full(len(rows), -1, np.int32)
    s, i, v = kops.topk_cosine(q, table, k, exclude_rows=excl,
                               use_pallas=False, norms=norms,
                               block_rows=SLAB)
    return np.asarray(s), np.asarray(i), np.asarray(v)


def _resident(table, rows, k, exclude_self):
    norms = np.linalg.norm(table, axis=1).astype(np.float32)
    res = kops.ResidentTable(table, norms, SLAB)
    s, i = jax.device_get(kops.topk_cosine_resident(
        res, jnp.asarray(rows), k, exclude_self=exclude_self))
    excl = rows if exclude_self else np.full(len(rows), -1, np.int32)
    return s, i, kops.valid_counts(excl, table.shape[0], k)


def _assert_same(got, want):
    s, i, v = got
    sr, ir, vr = want
    np.testing.assert_array_equal(v, vr)
    assert s.shape == sr.shape and i.shape == ir.shape
    for r in range(len(v)):
        np.testing.assert_array_equal(i[r, :v[r]], ir[r, :v[r]])
        np.testing.assert_array_equal(s[r, :v[r]], sr[r, :v[r]])
        assert (i[r, :v[r]] < N).all()          # no pad row surfaces


@pytest.mark.parametrize("exclude_self", [True, False])
@pytest.mark.parametrize("k", [1, 10, N + 15])
@pytest.mark.parametrize("q", [1, 2, 4, 8, 16, 32])
def test_resident_matches_streaming_bit_for_bit(q, k, exclude_self):
    table = _table()
    rows = np.random.default_rng(q * 100 + k).integers(
        0, N, q).astype(np.int32)
    rows[-1] = N - 1                        # a query row in the padded slab
    got = _resident(table, rows, k, exclude_self)
    _assert_same(got, _streamed(table, rows, k, exclude_self))
    if exclude_self:
        for r, row in enumerate(rows):
            assert row not in got[1][r, :got[2][r]]


@pytest.mark.parametrize("exclude_self", [True, False])
def test_resident_ties_go_to_the_lower_index(exclude_self):
    """Rows 3, 20 and 40 are one vector (across three slabs), as are
    rows 17 and 33: equal scores must come out lowest index first."""
    table = _table(seed=11)
    table[[20, 40]] = table[3]
    table[33] = table[17]
    rows = np.array([3, 17, 5, 40], np.int32)
    got = _resident(table, rows, 6, exclude_self)
    _assert_same(got, _streamed(table, rows, 6, exclude_self))
    s, i, v = got
    copies = [3, 20, 40] if not exclude_self else [20, 40]
    assert list(i[0, :len(copies)]) == copies
    assert list(i[3, :2]) == [3, 20]
    assert i[1, 0] == (33 if exclude_self else 17)


@pytest.mark.parametrize("k", [1, 10, N])
@pytest.mark.parametrize("q", [1, 8, 32])
def test_resident_ids_match_the_float64_oracle(q, k):
    table = _table(seed=5)
    unit = (table / np.linalg.norm(table.astype(np.float64), axis=1,
                                   keepdims=True)).astype(np.float32)
    rows = np.random.default_rng(q + k).integers(0, N, q).astype(np.int32)
    s, i, v = _resident(table, rows, k, True)
    _, ir, vr = ref.topk_cosine_ref(jnp.asarray(unit[rows]),
                                    jnp.asarray(unit), k,
                                    exclude_rows=jnp.asarray(rows))
    ir, vr = np.asarray(ir), np.asarray(vr)
    np.testing.assert_array_equal(v, vr)
    for r in range(q):
        np.testing.assert_array_equal(i[r, :v[r]], ir[r, :vr[r]])


def _publish(registry, n=N, version="2024-01"):
    ids = [f"GO:{i:07d}" for i in range(n)]
    registry.publish("go", version, "transe", ids,
                     [f"go term {i}" for i in range(n)], _table(n=n),
                     ontology_checksum=f"ck-{version}",
                     hyperparameters={"dim": D})
    return ids


def _table_bytes(n=N):
    return n * D * 4 + n * 4


CAPACITY = 2

#: regime -> the device limit ``device_bytes_limit`` reports
LIMITS = {
    "fits": lambda: 2 * CAPACITY * _table_bytes(),        # allowance == table
    "over": lambda: 2 * CAPACITY * _table_bytes() - 1,    # one byte short
    "no_stats": lambda: None,                             # the CPU backend
}


@pytest.mark.parametrize("regime", sorted(LIMITS))
def test_engine_keeps_a_table_resident_only_when_it_fits(registry,
                                                         monkeypatch,
                                                         regime):
    ids = _publish(registry)
    monkeypatch.setattr(kops, "STREAM_BLOCK_ROWS", SLAB)
    monkeypatch.setattr(kops, "device_bytes_limit", LIMITS[regime])
    engine = ServingEngine(registry, cache_capacity=CAPACITY)
    index = engine._index("go", "transe")
    index.top_k_rows([0, 1], 5)                      # compile outside
    before = kops.stream_counts()
    hits = index.top_k_rows([2, 3], 5)
    after = kops.stream_counts()
    assert [len(h) for h in hits] == [5, 5]
    resident = regime == "fits"
    assert (index.device_table_bytes() > 0) == resident
    if resident:
        # three 16-row slabs: the table padded to 48 rows
        assert index.device_table_bytes() == 48 * (D + 1) * 4
        assert after["resident_calls"] == before["resident_calls"] + 1
        assert after["blocks"] == before["blocks"]
        assert after["calls"] == before["calls"]
    else:
        assert after["resident_calls"] == before["resident_calls"]
        assert after["blocks"] == before["blocks"] + 3
        assert after["calls"] == before["calls"] + 1
    # either way the answers are the streamed ones
    solo = EmbeddingIndex(ids, ids, _table(), block_rows=SLAB)
    want = solo.top_k_rows([2, 3], 5)
    assert [[(c.identifier, c.score) for c in h] for h in hits] == \
        [[(c.identifier, c.score) for c in h] for h in want]


def test_pallas_engine_keeps_streaming(registry, monkeypatch):
    _publish(registry)
    monkeypatch.setattr(kops, "device_bytes_limit", LIMITS["fits"])
    engine = ServingEngine(registry, cache_capacity=CAPACITY,
                           use_pallas=True)
    assert engine._index("go", "transe").device_table_bytes() == 0


def test_flush_on_a_resident_engine_launches_once_per_batch(registry,
                                                            monkeypatch):
    ids = _publish(registry)
    monkeypatch.setattr(kops, "STREAM_BLOCK_ROWS", SLAB)
    monkeypatch.setattr(kops, "device_bytes_limit", LIMITS["fits"])
    engine = ServingEngine(registry, cache_capacity=CAPACITY)
    sched = BatchScheduler(engine, max_batch=4)
    engine._index("go", "transe")
    before = kops.stream_counts()
    tickets = [sched.submit(TopKRequest("go", "transe", ids[i], 5))
               for i in range(6)]
    results = sched.flush()
    assert all(len(results[t.id]) == 5 for t in tickets)
    st = sched.stats
    d = {k: kops.stream_counts()[k] - before[k] for k in before}
    assert st["batches"] - st["sim_batches"] == 2
    assert d["resident_calls"] == 2
    assert d["calls"] == d["blocks"] == 0
    assert d["stage_s"] == 0.0 and d["put_s"] == 0.0
    assert st["batch_s"] >= st["topk_s"] > 0.0


def test_knn_join_on_a_resident_index_matches_per_row_top_k():
    table = _table(seed=3)
    ids = [f"X:{i}" for i in range(N)]
    index = EmbeddingIndex(ids, ids, table, block_rows=SLAB)
    index._make_resident()
    rows = list(range(0, N, 4))
    got = {}
    for start, hits in index.knn_join_rows(rows, 7, slab=5):
        for j, h in enumerate(hits):
            got[rows[start + j]] = h
    assert sorted(got) == rows
    for r in rows:
        want = index.top_k_rows([r], 7)[0]
        assert [c.identifier for c in got[r]] == \
            [c.identifier for c in want]
        np.testing.assert_allclose([c.score for c in got[r]],
                                   [c.score for c in want],
                                   rtol=1e-6, atol=1e-6)


def test_eviction_and_invalidation_drop_the_device_copy(registry,
                                                        monkeypatch):
    _publish(registry, version="2024-01")
    _publish(registry, version="2024-02")
    monkeypatch.setattr(kops, "device_bytes_limit", lambda: 1 << 40)
    engine = ServingEngine(registry, cache_capacity=1)
    old = weakref.ref(engine._index("go", "transe", "2024-01")._resident)
    newer = weakref.ref(engine._index("go", "transe", "2024-02")._resident)
    gc.collect()
    assert old() is None and newer() is not None     # LRU eviction
    assert engine.drop_version("go", "2024-02") == 1
    gc.collect()
    assert newer() is None                           # invalidation


def test_resident_call_is_spanned(tmp_path):
    from benchmarks.chip import tracing

    ids = [f"X:{i}" for i in range(N)]
    index = EmbeddingIndex(ids, ids, _table(), block_rows=SLAB)
    index._make_resident()
    index.top_k_rows([1, 2], 3)                      # compile outside
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        index.top_k_rows([3, 4], 3)
    finally:
        jax.profiler.stop_trace()
    names = {name for name, _, _ in
             tracing.extract(tracing.find_xplane(str(tmp_path)))["host"]}
    for want in ("index.queries", "index.launch", "index.fetch",
                 "index.decode"):
        assert want in names, want
    assert not names & {"stream.stage", "stream.put", "stream.step"}
