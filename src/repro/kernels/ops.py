"""jit'd public wrappers for the Pallas kernels.

``use_pallas`` dispatch: the pure-jnp reference path is the default and
the shape source of truth; REPRO_USE_PALLAS=1 (or use_pallas=True) routes
the same call sites through the Pallas kernels.  Whether a kernel runs
compiled or interpreted is decided when it is called
(``topk_similarity.default_interpret``): compiled on a TPU, interpreted on any other backend
(the CPU test runs).

Scoring dots run at ``Precision.HIGHEST``: on a TPU a float32 matmul at
default precision takes one bfloat16 pass, which moves cosine scores by
~1e-3 and reorders near neighbours.  On the CPU the flag changes nothing.

Table residency.  Three layouts serve one top-k contract:

* **streamed** — ``topk_cosine`` on a host table (``np.ndarray`` /
  ``np.memmap``) never puts the whole (N, d) array on device.  The host
  loop walks the table in fixed ``block_rows`` slabs — each slab is
  transferred, scored (with the sidecar ``norms`` folded into the kernel,
  so no unit copy exists on *either* side), and merged into a running
  (Q, k) top-k.  Peak device allocation is O(block_rows·d + Q·k)
  regardless of N; ``stream_stats`` records it so the scale bench can
  assert the bound.
* **resident** — ``ResidentTable`` puts the raw rows and norms on one
  device once, padded to a multiple of the slab, and
  ``topk_cosine_resident`` serves a batch in one launch: the query rows
  are sliced out and normalised on device, then a ``fori_loop`` runs the
  streamed path's slab step over the resident slabs.  Same step, same
  shapes, same tie order; no table bytes cross the host link per batch.
* **sharded** — on a multi-device mesh the table lives sharded in HBM
  (``shard_table_raw`` / ``topk_cosine_sharded``).

A jnp-array table passed to ``topk_cosine`` keeps the single-launch
device path.
"""
from __future__ import annotations

import functools
import os
import threading
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from . import ref
from .kge_score import kge_score_pallas
from .swa_attention import swa_attention_pallas
from .topk_similarity import default_interpret as _interpret
from .topk_similarity import topk_cosine_pallas

_ENV_FLAG = os.environ.get("REPRO_USE_PALLAS", "0") == "1"

#: precision of every scoring dot (see the module docstring)
_SCORE_PRECISION = jax.lax.Precision.HIGHEST

#: host-block size for the streaming top-k driver: 8192 rows × 200 dims ×
#: 4 B ≈ 6.6 MB per transfer — large enough to amortize dispatch, small
#: enough that a dozen concurrent streams fit VMEM-scale budgets
STREAM_BLOCK_ROWS = 8192

#: in-shard block size for the blocked ref path inside the sharded merge
SHARD_BLOCK_N = 1024

#: cumulative streaming-driver counters (reset with reset_stream_stats):
#: ``peak_block_bytes`` is the largest single device transfer (table block
#: + norms block) any streamed call made — the scale bench asserts it stays
#: O(block_rows·d), i.e. no full-table private device copy ever happened;
#: ``stage_s`` is host time spent copying slabs out of the table into
#: staging buffers, ``put_s`` host time spent handing them to the device;
#: ``resident_calls`` counts top-k launches served from a resident table
#: (they add nothing to the streaming counters).
#: Updated under ``_stream_lock``: the flush loop and the job executor
#: stream concurrently.
stream_stats = {"calls": 0, "blocks": 0, "peak_block_bytes": 0,
                "stage_s": 0.0, "put_s": 0.0, "resident_calls": 0}
_stream_lock = threading.Lock()


def stream_counts() -> dict:
    """A consistent copy of ``stream_stats`` (for ``/stats``)."""
    with _stream_lock:
        return dict(stream_stats)


def reset_stream_stats() -> None:
    with _stream_lock:
        stream_stats.update({"calls": 0, "blocks": 0, "peak_block_bytes": 0,
                             "stage_s": 0.0, "put_s": 0.0,
                             "resident_calls": 0})


def _use_pallas(flag: Optional[bool]) -> bool:
    return _ENV_FLAG if flag is None else flag


def _stream_step(q, blk, nrm, offset, limit, excl, run_s, run_i, *,
                 k: int, has_norms: bool):
    """Score one (block_rows, d) slab and merge it into the running top-k.

    ``offset``/``limit`` are traced scalars (block start, real table rows),
    so every block of every same-shaped table reuses one compiled step.
    Tie-order matches the one-shot oracle: running entries concatenate
    first and always carry lower global indices than the current block
    (blocks ascend), so equal scores resolve to the lower global index —
    exactly ``lax.top_k`` over the full score matrix.
    """
    if has_norms:
        blk = blk / jnp.maximum(nrm[:, None], 1e-12)
    s = jnp.matmul(q, blk.T, precision=_SCORE_PRECISION)   # (Q, bs)
    col = offset + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < limit, s, ref.NEG_INF)             # tail-pad rows
    s = jnp.where(col == excl[:, None], ref.NEG_INF, s)    # self-exclusion
    cand_s = jnp.concatenate([run_s, s], axis=1)
    cand_i = jnp.concatenate([run_i, col], axis=1)
    s2, pos = jax.lax.top_k(cand_s, k)
    return s2, jnp.take_along_axis(cand_i, pos, axis=1)


#: the streamed path's per-slab launch
_stream_step_ref = jax.jit(_stream_step, static_argnames=("k", "has_norms"))


@functools.partial(jax.jit, static_argnames=("k",))
def _stream_merge(run_s, run_i, blk_s, blk_i, offset, *, k: int):
    """Fold one block's local top-k (pallas backend) into the running
    top-k; local indices shift by ``offset`` to global.  Same concat order
    (running first) as ``_stream_step_ref`` — same tie semantics."""
    cand_s = jnp.concatenate([run_s, blk_s], axis=1)
    cand_i = jnp.concatenate([run_i, blk_i + offset], axis=1)
    s2, pos = jax.lax.top_k(cand_s, k)
    return s2, jnp.take_along_axis(cand_i, pos, axis=1)


def _topk_stream(q_unit, e_table: np.ndarray, k: int, exclude_rows,
                 norms, use_pallas: bool, block_rows: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Host-block streaming driver over an ``np.ndarray``/``np.memmap``
    table: per block, copy a (bs, d) slab host→device, score it (norms
    folded in-kernel), merge into the running (Q, k') top-k.  The table is
    never resident on device and never normalized as a whole anywhere."""
    n, d = e_table.shape
    qn = q_unit.shape[0]
    k_c = min(int(k), n)
    bs = min(int(block_rows), n)
    if exclude_rows is None:
        excl_np = np.full((qn,), -1, np.int32)
    else:
        excl_np = np.asarray(exclude_rows, np.int32)
    excl = jnp.asarray(excl_np)
    q = jnp.asarray(q_unit, jnp.float32)
    has_norms = norms is not None
    norms_np = None if norms is None else np.asarray(norms)

    # a function-level import: the repro.core package imports serving,
    # which imports this module
    from ..core.metrics import span

    run_s = jnp.full((qn, k_c), ref.NEG_INF, jnp.float32)
    run_i = jnp.zeros((qn, k_c), jnp.int32)
    limit = jnp.int32(n)
    peak = 0
    n_blocks = 0
    # host seconds of this call, added to stream_stats once at the end
    times = {"stage_s": 0.0, "put_s": 0.0}
    for start in range(0, n, bs):
        rows = min(bs, n - start)
        if use_pallas:
            # the pallas kernel tiles internally and masks past its own
            # n_real, so hand it exactly the real rows of this slab
            with span("stream.stage", times, "stage_s"):
                blk_host = np.ascontiguousarray(
                    e_table[start:start + rows], dtype=np.float32)
                nrm_host = (np.ascontiguousarray(
                    norms_np[start:start + rows], dtype=np.float32)
                    if has_norms else None)
            with span("stream.put", times, "put_s"):
                blk = jnp.asarray(blk_host)
                nrm = jnp.asarray(nrm_host) if has_norms else None
            loc = np.where((excl_np >= start) & (excl_np < start + rows),
                           excl_np - start, -1).astype(np.int32)
            kb = min(k_c, rows)
            bn = min(1024, max(128, rows))
            with span("stream.step"):
                blk_s, blk_i, _ = topk_cosine_pallas(
                    q, blk, kb, exclude_rows=jnp.asarray(loc), norms=nrm,
                    block_n=bn)
                run_s, run_i = _stream_merge(run_s, run_i, blk_s, blk_i,
                                             jnp.int32(start), k=k_c)
            peak = max(peak, rows * d * 4 + (rows * 4 if has_norms else 0))
        else:
            # fixed-size slab (tail zero-padded) → one jitted step shape.
            # The staging arrays MUST be freshly allocated per block:
            # jnp.asarray can adopt an aligned numpy buffer zero-copy on
            # CPU, so a reused scratch array would be rewritten under the
            # previous (async-dispatched) step and merge the wrong rows.
            with span("stream.stage", times, "stage_s"):
                blk_host = np.zeros((bs, d), np.float32)
                blk_host[:rows] = e_table[start:start + rows]
                nrm_host = np.ones((bs,), np.float32)
                if has_norms:
                    nrm_host[:rows] = norms_np[start:start + rows]
            with span("stream.put", times, "put_s"):
                blk = jnp.asarray(blk_host)
                nrm = jnp.asarray(nrm_host)
            with span("stream.step"):
                run_s, run_i = _stream_step_ref(
                    q, blk, nrm, jnp.int32(start), limit, excl, run_s, run_i,
                    k=k_c, has_norms=has_norms)
            peak = max(peak, bs * d * 4 + bs * 4)
        n_blocks += 1
    with _stream_lock:
        stream_stats["calls"] += 1
        stream_stats["blocks"] += n_blocks
        stream_stats["peak_block_bytes"] = max(
            stream_stats["peak_block_bytes"], peak)
        stream_stats["stage_s"] += times["stage_s"]
        stream_stats["put_s"] += times["put_s"]
    return run_s, run_i, jnp.asarray(valid_counts(excl_np, n, k_c))


def valid_counts(exclude_rows, n: int, k: int) -> np.ndarray:
    """Real entries per query row, on the host: ``min(k, n)``, one fewer
    where the query's excluded row lies in the table."""
    excl = np.asarray(exclude_rows)
    excluded = ((excl >= 0) & (excl < n)).astype(np.int32)
    return np.minimum(min(int(k), n), n - excluded).astype(np.int32)


def device_bytes_limit() -> Optional[int]:
    """Bytes the default device reports it can hold
    (``memory_stats()["bytes_limit"]``); None where the backend reports
    no memory stats, as the CPU backend does."""
    stats = jax.local_devices()[0].memory_stats()
    if not stats or "bytes_limit" not in stats:
        return None
    return int(stats["bytes_limit"])


class ResidentTable:
    """A raw (N, d) table and its per-row norms held on the default
    device, padded once to a multiple of the slab (zero rows, norms of 1,
    as :func:`shard_table_raw` pads) so no launch copies the table."""

    def __init__(self, e_table, norms, block_rows: Optional[int] = None):
        n, d = e_table.shape
        self.n = int(n)
        #: the slab the streamed path would walk this table in
        self.block_rows = max(1, min(int(block_rows or STREAM_BLOCK_ROWS),
                                     self.n))
        n_pad = -self.n % self.block_rows
        table = np.zeros((self.n + n_pad, d), np.float32)
        table[:self.n] = e_table
        nrm = np.ones((self.n + n_pad,), np.float32)
        nrm[:self.n] = norms
        self.table = jax.device_put(table)
        self.norms = jax.device_put(nrm)
        self.limit = jax.device_put(np.int32(self.n))

    @property
    def nbytes(self) -> int:
        return int(self.table.nbytes + self.norms.nbytes)


@functools.partial(jax.jit,
                   static_argnames=("k", "block_rows", "exclude_self"))
def _resident_topk(table, norms, limit, rows, *, k: int, block_rows: int,
                   exclude_self: bool):
    """One launch over a resident table (:class:`ResidentTable`): slice
    out and normalise the query rows, then walk the table's slabs with
    the streamed path's step (:func:`_stream_step`) in a ``fori_loop`` —
    the same arithmetic on the same shapes, slabs in ascending order, so
    ties resolve to the lower index exactly as they do streamed."""
    qn = rows.shape[0]
    # one slice per query, unrolled: under a gather or a fori_loop the
    # TPU compiler stages the whole table into VMEM on every launch
    q = jnp.concatenate([jax.lax.dynamic_slice_in_dim(table, rows[i], 1)
                         for i in range(qn)])
    q = q / jnp.maximum(norms[rows][:, None], 1e-12)
    excl = rows if exclude_self else jnp.full_like(rows, -1)
    run = (jnp.full((qn, k), ref.NEG_INF, jnp.float32),
           jnp.zeros((qn, k), jnp.int32))

    def body(b, carry):
        start = b * block_rows
        blk = jax.lax.dynamic_slice_in_dim(table, start, block_rows)
        nrm = jax.lax.dynamic_slice_in_dim(norms, start, block_rows)
        return _stream_step(q, blk, nrm, start, limit, excl, *carry, k=k,
                            has_norms=True)

    return jax.lax.fori_loop(0, table.shape[0] // block_rows, body, run)


def topk_cosine_resident(res: ResidentTable, rows, k: int,
                         exclude_self: bool = True
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k of the table's own ``rows`` (an int32 device array) against
    the resident table: (scores, indices), each (Q, min(k, N)), on the
    device.  Entries past :func:`valid_counts` are sentinel padding."""
    out = _resident_topk(res.table, res.norms, res.limit, rows,
                         k=min(int(k), res.n), block_rows=res.block_rows,
                         exclude_self=bool(exclude_self))
    with _stream_lock:
        stream_stats["resident_calls"] += 1
    return out


def topk_cosine(q_unit, e_table, k: int,
                exclude_rows=None,
                use_pallas: Optional[bool] = None,
                norms=None,
                block_rows: Optional[int] = None,
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(Q, d) x (N, d) -> (scores, indices, valid), descending per row.

    k is clamped to N; ``exclude_rows`` (−1 = none) masks one table row per
    query inside the kernel; entries past ``valid[q]`` are sentinel padding
    that callers must not surface.

    ``e_table`` may be a host ``np.ndarray``/``np.memmap`` — then the
    streaming driver above runs (norms folded in-kernel, O(block) device
    scratch).  A jnp array takes the single-launch device path, unchanged
    from the pre-streaming contract.  ``norms`` (per-row L2) lets both
    paths score a raw, un-normalized table.
    """
    if isinstance(e_table, np.ndarray) and not isinstance(e_table, jnp.ndarray):
        return _topk_stream(q_unit, e_table, k, exclude_rows=exclude_rows,
                            norms=norms, use_pallas=_use_pallas(use_pallas),
                            block_rows=block_rows or STREAM_BLOCK_ROWS)
    if _use_pallas(flag=use_pallas):
        block_n = min(1024, max(128, e_table.shape[0]))
        return topk_cosine_pallas(q_unit, e_table, k,
                                  exclude_rows=exclude_rows, norms=norms,
                                  block_n=block_n)
    if norms is not None:
        return ref.topk_cosine_blocked_ref(
            q_unit, e_table, k, exclude_rows=exclude_rows, norms=norms,
            block_n=min(SHARD_BLOCK_N, max(128, e_table.shape[0])))
    return ref.topk_cosine_ref(q_unit, e_table, k, exclude_rows=exclude_rows)


def topk_cosine_join(q_unit, e_table, k: int,
                     exclude_rows=None,
                     norms=None,
                     use_pallas: Optional[bool] = None,
                     query_block_rows: int = 256,
                     block_rows: Optional[int] = None):
    """Slab-iterated all-pairs kNN join: generator over query slabs.

    Walks the (Q, d) query block in fixed ``query_block_rows`` slabs and
    runs each through :func:`topk_cosine` (streaming table residency when
    ``e_table`` is a host array), yielding ``(start, scores, indices,
    valid)`` with the slab's results trimmed to its real rows.  Peak
    allocation is O(query_block · table_block + query_block · k) no matter
    how long the join list is, and the caller regains control between
    slabs — the job executor uses that boundary to publish progress,
    observe cancellation, and yield to interactive traffic.

    The final partial slab is zero-padded up to ``query_block_rows``
    (pad exclusions −1) so every slab reuses one compiled step shape;
    pad rows are dropped before yielding.  Row results match a serial
    per-query :func:`topk_cosine` call: the same neighbour ids, and scores
    equal up to float32 rounding (a slab matmul and a one-row matmul may
    sum the d products in a different order, so the last ulp can differ).
    """
    q = np.asarray(q_unit, np.float32)
    qn = q.shape[0]
    s = max(1, int(query_block_rows))
    if exclude_rows is None:
        excl_np = np.full((qn,), -1, np.int32)
    else:
        excl_np = np.asarray(exclude_rows, np.int32)
    for start in range(0, qn, s):
        rows = min(s, qn - start)
        q_slab = q[start:start + rows]
        e_slab = excl_np[start:start + rows]
        if rows < s:
            q_slab = np.concatenate(
                [q_slab, np.zeros((s - rows, q.shape[1]), np.float32)])
            e_slab = np.concatenate(
                [e_slab, np.full((s - rows,), -1, np.int32)])
        sc, ix, va = topk_cosine(q_slab, e_table, k, exclude_rows=e_slab,
                                 use_pallas=use_pallas, norms=norms,
                                 block_rows=block_rows)
        yield (start, np.asarray(sc)[:rows], np.asarray(ix)[:rows],
               np.asarray(va)[:rows])


def mesh_data_shards(mesh, axis: str = "data") -> int:
    """Number of table shards a mesh provides (1 = no sharding)."""
    if mesh is None:
        return 1
    return int(dict(mesh.shape).get(axis, 1))


def shard_table(e_unit: jnp.ndarray, mesh, axis: str = "data"
                ) -> Tuple[jnp.ndarray, int]:
    """Lay an (N, d) table out ``P(axis, None)`` across the mesh devices.

    N is zero-padded to a multiple of the axis size (shard_map needs even
    row blocks); returns ``(sharded table, n_valid)`` where ``n_valid`` is
    the real row count — pass both to :func:`topk_cosine_sharded`.
    """
    shards = mesh_data_shards(mesh, axis)
    e = jnp.asarray(e_unit, jnp.float32)
    pad = -e.shape[0] % shards
    if pad:
        e = jnp.concatenate([e, jnp.zeros((pad, e.shape[1]), e.dtype)], axis=0)
    return jax.device_put(e, NamedSharding(mesh, P(axis, None))), int(e_unit.shape[0])


def shard_table_raw(e_table, norms, mesh, axis: str = "data"
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """:func:`shard_table` for a *raw* (un-normalized) table plus its
    per-row L2 norms: rows are zero-padded, norms are one-padded (so pad
    rows stay zero after the in-kernel division), and both land sharded
    ``P(axis, …)`` on the mesh.  Returns ``(table, norms, n_valid)`` —
    pass all three (norms via ``norms=``) to :func:`topk_cosine_sharded`,
    which then normalizes each in-shard block in-kernel: no full unit copy
    exists on any device.
    """
    shards = mesh_data_shards(mesh, axis)
    e = jnp.asarray(e_table, jnp.float32)
    nrm = jnp.asarray(norms, jnp.float32)
    pad = -e.shape[0] % shards
    if pad:
        e = jnp.concatenate([e, jnp.zeros((pad, e.shape[1]), e.dtype)], axis=0)
        nrm = jnp.concatenate([nrm, jnp.ones((pad,), nrm.dtype)])
    return (jax.device_put(e, NamedSharding(mesh, P(axis, None))),
            jax.device_put(nrm, NamedSharding(mesh, P(axis))),
            int(e_table.shape[0]))


@functools.lru_cache(maxsize=128)
def _sharded_topk_fn(mesh, axis: str, n_real: int, n_total: int, k: int,
                     use_pallas: bool, interpret: bool, has_norms: bool):
    """Build (and cache) the jitted sharded top-k for one table layout.

    Each shard runs the existing single-device kernel contract on its
    (local_n, d) row block — global ``exclude_rows`` are translated to
    shard-local coordinates (−1 when the excluded row lives elsewhere) —
    then every device gathers all shards' candidates and merges them to
    the global top-k.  The merge runs inside the ``shard_map`` and its
    output is replicated, so no array sharded along the merged axis ever
    reaches ``lax.top_k`` — legal under Auto and Explicit mesh axes alike.

    Shard-merge invariants:
      * local fetch depth is ``min(k + n_pad, local_n)``: the zero rows
        padding N up to a shard multiple can occupy at most ``n_pad``
        local top-k slots (all in the last shard), so fetching that many
        extras guarantees every global top-k row survives its shard;
      * pad candidates (global index >= n_real) are masked to −inf after
        the local top-k, never surfaced;
      * ``valid`` is computed globally — min(k', N − excluded) with
        k' = min(k, N) — identical to the single-device contract.
    """
    shards = mesh_data_shards(mesh, axis)
    local_n = n_total // shards
    n_pad = n_total - n_real
    k_c = min(k, n_real)
    k_fetch = min(k + n_pad, local_n)

    def local_topk(q, e_loc, nrm_loc, excl):
        off = jax.lax.axis_index(axis).astype(jnp.int32) * local_n
        loc = jnp.where((excl >= off) & (excl < off + local_n),
                        excl - off, -1).astype(jnp.int32)
        block_n = min(SHARD_BLOCK_N, max(128, local_n))
        if use_pallas:
            s, i, _ = topk_cosine_pallas(
                q, e_loc, k_fetch, exclude_rows=loc,
                norms=nrm_loc if has_norms else None,
                block_n=block_n, interpret=interpret)
        elif has_norms:
            # blocks-within-shards: the blocked ref walks this shard's
            # rows in O(block_n) tiles, normalizing each tile in-kernel
            s, i, _ = ref.topk_cosine_blocked_ref(
                q, e_loc, k_fetch, exclude_rows=loc, norms=nrm_loc,
                block_n=block_n)
        else:
            s, i, _ = ref.topk_cosine_ref(q, e_loc, k_fetch, exclude_rows=loc)
        gi = i + off
        s = jnp.where(gi < n_real, s, ref.NEG_INF)
        # shard order = global row order, so top_k's lower-position-wins
        # tie rule keeps the lower global index, as the one-shot oracle does
        cand_s = jax.lax.all_gather(s, axis, axis=1, tiled=True)
        cand_i = jax.lax.all_gather(gi, axis, axis=1, tiled=True)
        s, pos = jax.lax.top_k(cand_s, k_c)       # over (Q, shards * k_fetch)
        return s, jnp.take_along_axis(cand_i, pos, axis=1)

    # check_vma=False: the merged result is equal on every device, but
    # all_gather's output is still typed as varying over ``axis``
    mapped = jax.shard_map(
        local_topk, mesh=mesh,
        in_specs=(P(None, None), P(axis, None), P(axis), P(None)),
        out_specs=(P(None, None), P(None, None)), check_vma=False)

    @jax.jit
    def run(q, e, nrm, excl):
        s, i = mapped(q, e, nrm, excl)
        excluded = ((excl >= 0) & (excl < n_real)).astype(jnp.int32)
        valid = jnp.minimum(k_c, n_real - excluded)
        return s, i, valid

    return run


def topk_cosine_sharded(q_unit: jnp.ndarray, e_unit: jnp.ndarray, k: int,
                        exclude_rows: Optional[jnp.ndarray] = None,
                        mesh=None, axis: str = "data",
                        n_valid: Optional[int] = None,
                        use_pallas: Optional[bool] = None,
                        norms: Optional[jnp.ndarray] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Device-sharded :func:`topk_cosine`: the (N, d) table is split in row
    blocks across the mesh's ``axis`` devices, each shard computes a local
    top-k via the single-device kernel contract, and a final merge reduces
    shard candidates to the global top-k.

    ``e_unit`` may carry zero-row padding (``n_valid`` = real rows; use
    :func:`shard_table` — or :func:`shard_table_raw` with ``norms`` for a
    raw table normalized in-kernel per block). Falls back to the
    single-device path — bit-identical contract — when the mesh has one
    device (or none) on ``axis``.
    """
    n_total = e_unit.shape[0]
    n_real = n_total if n_valid is None else int(n_valid)
    shards = mesh_data_shards(mesh, axis)
    if shards <= 1:
        return topk_cosine(q_unit, e_unit[:n_real], k,
                           exclude_rows=exclude_rows, use_pallas=use_pallas,
                           norms=None if norms is None else norms[:n_real])
    if n_total % shards:
        raise ValueError(
            f"table rows ({n_total}) must divide the {axis!r} axis "
            f"({shards}); lay the table out with shard_table()")
    qn = q_unit.shape[0]
    if exclude_rows is None:
        exclude_rows = jnp.full((qn,), -1, jnp.int32)
    has_norms = norms is not None
    if has_norms:
        nrm = jnp.asarray(norms, jnp.float32)
    else:
        # uniform operand shape keeps one cached shard_map program; the
        # has_norms static flag skips the division entirely
        nrm = jnp.ones((n_total,), jnp.float32)
        nrm = jax.device_put(nrm, NamedSharding(mesh, P(axis)))
    run = _sharded_topk_fn(mesh, axis, n_real, n_total, int(k),
                           _use_pallas(flag=use_pallas), _interpret(),
                           has_norms)
    return run(q_unit.astype(jnp.float32), e_unit, nrm,
               jnp.asarray(exclude_rows, jnp.int32))


def kge_score(h, r, t, neg, corrupt_head, model: str = "transe_l1",
              use_pallas: Optional[bool] = None):
    """Fused positive+negative KGE scoring. Returns (pos (B,), neg (B, K))."""
    if _use_pallas(flag=use_pallas):
        return kge_score_pallas(h, r, t, neg, corrupt_head, model=model,
                                interpret=_interpret())
    return ref.kge_score_ref(h, r, t, neg, corrupt_head, model=model)


def swa_attention(q, k, v, window: int, q_offset: int = 0,
                  use_pallas: Optional[bool] = None):
    """Sliding-window GQA attention.

    Accepts (B, H, S, d) tensors (ref layout); the pallas path folds heads.
    """
    if _use_pallas(flag=use_pallas):
        b, hq, sq, d = q.shape
        _, hkv, skv, _ = k.shape
        qf = q.reshape(b * hq, sq, d)
        kf = k.reshape(b * hkv, skv, d)
        vf = v.reshape(b * hkv, skv, d)
        out = swa_attention_pallas(qf, kf, vf, window=window, q_offset=q_offset,
                                   interpret=_interpret())
        return out.reshape(b, hq, sq, d)
    return ref.swa_attention_ref(q, k, v, window=window, q_offset=q_offset)
