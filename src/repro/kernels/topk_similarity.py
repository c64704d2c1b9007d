"""Fused cosine top-k Pallas kernel — the Bio-KGvec2go serving hot spot.

The paper's *top closest concepts* endpoint scans all N class vectors per
query. TPU adaptation: stream the (N, d) table through VMEM in
(block_n, d) slabs, compute q·Eᵀ on the MXU per slab, and keep a running
top-k (scores + global indices) in VMEM across grid steps — one HBM pass
over the table, no (Q, N) score matrix ever materialized.

Grid: (N // block_n,) — sequential on TPU, so the output block is safely
revisited and acts as the running accumulator. The merge is k rounds of
(max, lowest-index-of-max, mask) over the running (Q, k) top-k and the
(Q, block_n) block scores, with lane-iota selects and masked reductions
only: the TPU lowering has no scatter, and the two widths are never
concatenated along lanes.

Serving-correctness contract (PR 1):
  * ``exclude_rows`` — per-query table row to mask out (−1 for none). The
    serving layer uses this for self-exclusion, replacing the old
    "ask for k+1 then filter in Python" dance, which silently returned
    k−1 results whenever the query row was *not* in the top k+1.
  * ``k`` is clamped to N at trace time, and a per-query ``valid`` count
    is returned: entries ``[valid:]`` of a row are sentinel padding
    (score −1e30, index 0) and must not be surfaced. Before this, k > N
    leaked sentinel rows pointing at entity 0 into API responses.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def default_interpret() -> bool:
    """Pallas interpret mode: never on a TPU, always on any other backend.
    Asked when a kernel is called, so importing this package starts no
    backend."""
    return jax.default_backend() != "tpu"


def _topk_kernel(q_ref, e_ref, n_ref, x_ref, out_s_ref, out_i_ref, out_v_ref,
                 *, k: int, block_n: int, n_real: int, has_norms: bool):
    step = pl.program_id(0)
    excl = x_ref[...]                    # (Q, 1) int32, -1 = no exclusion

    @pl.when(step == 0)
    def _init():
        out_s_ref[...] = jnp.full_like(out_s_ref, NEG_INF)
        out_i_ref[...] = jnp.zeros_like(out_i_ref)
        excluded = ((excl >= 0) & (excl < n_real)).astype(jnp.int32)
        out_v_ref[...] = jnp.minimum(k, n_real - excluded)

    q = q_ref[...]                       # (Q, d)
    e = e_ref[...]                       # (block_n, d)
    if has_norms:
        # fold the per-row L2 norms into the score: the exact float32 ops
        # EmbeddingIndex.unit_rows uses, so raw mmap rows + sidecar norms
        # score like a host-normalized table
        e = e / jnp.maximum(n_ref[...], 1e-12)            # (block_n, 1) bcast
    # q · eᵀ on the MXU, contracting d on both sides (no transpose);
    # HIGHEST keeps float32 products instead of one bf16 pass
    s = jax.lax.dot_general(q, e, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)  # (Q, block_n)
    col = step * block_n + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < n_real, s, NEG_INF)                   # mask pad rows
    s = jnp.where(col == excl, NEG_INF, s)                    # self-exclusion

    # k rounds over the running top-k and this block, kept apart (no lane
    # concat): each round takes the best score, and among equal scores the
    # lowest global index — lax.top_k's tie order over the full row.  The
    # winner is written to slot j by a lane-iota select and masked out by
    # index, so no gather or scatter appears.
    run_s = out_s_ref[...]               # (Q, k)
    run_i = out_i_ref[...]
    slot = jax.lax.broadcasted_iota(jnp.int32, run_s.shape, 1)
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    best_s = jnp.full(run_s.shape, NEG_INF, jnp.float32)
    best_i = jnp.zeros(run_i.shape, jnp.int32)
    for j in range(k):                   # unrolled: k is small & static
        m = jnp.maximum(jnp.max(run_s, axis=1, keepdims=True),
                        jnp.max(s, axis=1, keepdims=True))          # (Q, 1)
        pick = jnp.minimum(
            jnp.min(jnp.where(run_s == m, run_i, big), axis=1, keepdims=True),
            jnp.min(jnp.where(s == m, col, big), axis=1, keepdims=True))
        best_s = jnp.where(slot == j, m, best_s)
        best_i = jnp.where(slot == j, pick, best_i)
        run_s = jnp.where(run_i == pick, NEG_INF, run_s)
        s = jnp.where(col == pick, NEG_INF, s)
    out_s_ref[...] = best_s
    out_i_ref[...] = best_i


@functools.partial(jax.jit, static_argnames=("k", "block_n", "interpret"))
def topk_cosine_pallas(
    q_unit: jnp.ndarray,      # (Q, d) row-normalized queries
    e_unit: jnp.ndarray,      # (N, d) table — row-normalized unless norms given
    k: int,
    exclude_rows: Optional[jnp.ndarray] = None,   # (Q,) int32, -1 = none
    norms: Optional[jnp.ndarray] = None,          # (N,) per-row L2 norms
    block_n: int = 1024,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (scores (Q, k'), indices (Q, k'), valid (Q,)) with
    k' = min(k, N); rows are descending and entries past ``valid[q]`` are
    sentinel padding.  With ``norms``, ``e_unit`` may be the *raw* table
    and each streamed block is normalized in-kernel — no (N, d) unit copy
    ever exists.  ``interpret=None`` runs compiled on a TPU and interpreted
    on any other backend, decided when the call is traced."""
    if interpret is None:
        interpret = default_interpret()
    qn, d = q_unit.shape
    n = e_unit.shape[0]
    k = min(k, n)                        # static clamp: k never exceeds N
    if exclude_rows is None:
        exclude_rows = jnp.full((qn,), -1, jnp.int32)
    excl = jnp.asarray(exclude_rows, jnp.int32).reshape(qn, 1)
    has_norms = norms is not None
    # pad N to a block multiple with -inf-scoring rows (zero vectors);
    # pad norms with 1.0 so the pad rows stay zero after division
    n_pad = -n % block_n
    if n_pad:
        e_unit = jnp.concatenate(
            [e_unit, jnp.zeros((n_pad, d), e_unit.dtype)], axis=0
        )
    if has_norms:
        nrm = jnp.asarray(norms, jnp.float32).reshape(n, 1)
        if n_pad:
            nrm = jnp.concatenate([nrm, jnp.ones((n_pad, 1), nrm.dtype)])
    else:
        nrm = jnp.ones((n + n_pad, 1), jnp.float32)
    n_total = n + n_pad
    grid = (n_total // block_n,)

    out_s, out_i, out_v = pl.pallas_call(
        functools.partial(_topk_kernel, k=k, block_n=block_n, n_real=n,
                          has_norms=has_norms),
        grid=grid,
        in_specs=[
            pl.BlockSpec((qn, d), lambda i: (0, 0)),          # q resident
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),     # stream table
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),     # stream norms
            pl.BlockSpec((qn, 1), lambda i: (0, 0)),          # exclusions
        ],
        out_specs=[
            pl.BlockSpec((qn, k), lambda i: (0, 0)),          # running top-k
            pl.BlockSpec((qn, k), lambda i: (0, 0)),
            pl.BlockSpec((qn, 1), lambda i: (0, 0)),          # valid counts
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qn, k), jnp.float32),
            jax.ShapeDtypeStruct((qn, k), jnp.int32),
            jax.ShapeDtypeStruct((qn, 1), jnp.int32),
        ],
        interpret=interpret,
    )(q_unit.astype(jnp.float32), e_unit.astype(jnp.float32), nrm, excl)

    return out_s, out_i, out_v[:, 0]
