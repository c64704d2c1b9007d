"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics; kernels must match them (tests assert_allclose,
sweeping shapes and dtypes).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30

#: scoring dots keep float32 products on a TPU (default precision there
#: is one bfloat16 pass); the CPU ignores the flag
HIGHEST = jax.lax.Precision.HIGHEST


# --------------------------- topk_similarity --------------------------- #
def topk_cosine_blocked_ref(
    q_unit: jnp.ndarray,
    e_table: jnp.ndarray,
    k: int,
    exclude_rows: Optional[jnp.ndarray] = None,
    norms: Optional[jnp.ndarray] = None,
    block_n: int = 1024,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Blocked pure-jnp top-k: same contract as :func:`topk_cosine_ref`,
    computed over fixed (block_n, d) row tiles with a running top-k merge —
    scratch is O(block_n + k) regardless of N, so one jitted shape serves a
    100k-row table as well as a 1k-row one (it is also what each shard runs
    inside ``topk_cosine_sharded``: blocks-within-shards).

    ``norms`` (optional, per-row L2) folds normalization into the score:
    ``e_table`` may then be the raw mmap rows and each block is normalized
    with the exact float32 ops ``EmbeddingIndex.unit_rows`` uses, so the
    rows scored are bit-identical to pre-normalizing the full table on the
    host.

    Merge tie-order matches one-shot ``lax.top_k`` on the full score
    matrix: running entries are concatenated *before* the current block's
    candidates and blocks are visited in ascending row order, so among
    equal scores the lower global index always wins — same as the global
    argmax. (Entries past ``valid`` are sentinel padding and may differ
    from the one-shot oracle there; the contract forbids surfacing them.)
    """
    n, d = e_table.shape
    qn = q_unit.shape[0]
    k_c = min(k, n)
    if exclude_rows is None:
        excl = jnp.full((qn,), -1, jnp.int32)
    else:
        excl = jnp.asarray(exclude_rows, jnp.int32)
    q = jnp.asarray(q_unit, jnp.float32)
    e = jnp.asarray(e_table, jnp.float32)
    nrm = None if norms is None else jnp.asarray(norms, jnp.float32)
    n_pad = -n % block_n
    if n_pad:
        e = jnp.concatenate([e, jnp.zeros((n_pad, d), e.dtype)], axis=0)
        if nrm is not None:
            # pad norms with 1.0: pad rows are zero vectors, and the
            # col >= n mask below sends them to -inf anyway
            nrm = jnp.concatenate([nrm, jnp.ones((n_pad,), nrm.dtype)])
    n_blocks = (n + n_pad) // block_n
    iota = jax.lax.broadcasted_iota(jnp.int32, (qn, block_n), 1)

    def body(b, carry):
        run_s, run_i = carry
        blk = jax.lax.dynamic_slice(e, (b * block_n, 0), (block_n, d))
        if nrm is not None:
            nb = jax.lax.dynamic_slice(nrm, (b * block_n,), (block_n,))
            blk = blk / jnp.maximum(nb[:, None], 1e-12)
        s = jnp.matmul(q, blk.T, precision=HIGHEST)        # (Q, block_n)
        col = b * block_n + iota
        s = jnp.where(col < n, s, NEG_INF)                 # pad rows
        s = jnp.where(col == excl[:, None], NEG_INF, s)    # self-exclusion
        cand_s = jnp.concatenate([run_s, s], axis=1)
        cand_i = jnp.concatenate([run_i, col], axis=1)
        s2, pos = jax.lax.top_k(cand_s, k_c)
        return s2, jnp.take_along_axis(cand_i, pos, axis=1)

    run = (jnp.full((qn, k_c), NEG_INF, jnp.float32),
           jnp.zeros((qn, k_c), jnp.int32))
    s, i = jax.lax.fori_loop(0, n_blocks, body, run)
    excluded = ((excl >= 0) & (excl < n)).astype(jnp.int32)
    valid = jnp.minimum(k_c, n - excluded)
    return s, i, valid


def topk_cosine_ref(
    q_unit: jnp.ndarray,
    e_unit: jnp.ndarray,
    k: int,
    exclude_rows: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """q_unit (Q, d), e_unit (N, d), both row-normalized.

    Returns (scores (Q, k'), indices (Q, k'), valid (Q,)) sorted descending,
    with k' = min(k, N). ``exclude_rows`` masks one table row per query
    (-1 = none); entries past ``valid[q]`` are sentinel padding.
    """
    n = e_unit.shape[0]
    k = min(k, n)
    scores = jnp.matmul(q_unit, e_unit.T, precision=HIGHEST)
    if exclude_rows is None:
        excl = jnp.full((q_unit.shape[0],), -1, jnp.int32)
    else:
        excl = jnp.asarray(exclude_rows, jnp.int32)
    col = jnp.arange(n, dtype=jnp.int32)[None, :]
    scores = jnp.where(col == excl[:, None], NEG_INF, scores)
    s, i = jax.lax.top_k(scores, k)
    excluded = ((excl >= 0) & (excl < n)).astype(jnp.int32)
    valid = jnp.minimum(k, n - excluded)
    return s, i, valid


# ------------------------------ kge_score ------------------------------ #
def kge_score_ref(
    h: jnp.ndarray,            # (B, d) head embeddings
    r: jnp.ndarray,            # (B, d) relation embeddings
    t: jnp.ndarray,            # (B, d) tail embeddings
    neg: jnp.ndarray,          # (B, K, d) corrupting entity embeddings
    corrupt_head: jnp.ndarray, # (B, K) bool — True: neg replaces head
    model: str = "transe_l1",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused positive + negative scoring. Returns (pos (B,), neg (B, K))."""
    if model == "transe_l1":
        pos = -jnp.sum(jnp.abs(h + r - t), axis=-1)
        diff_h = neg + r[:, None, :] - t[:, None, :]    # neg as head
        diff_t = h[:, None, :] + r[:, None, :] - neg    # neg as tail
        diff = jnp.where(corrupt_head[..., None], diff_h, diff_t)
        negs = -jnp.sum(jnp.abs(diff), axis=-1)
    elif model == "transe_l2":
        pos = -jnp.sqrt(jnp.sum((h + r - t) ** 2, axis=-1) + 1e-12)
        diff_h = neg + r[:, None, :] - t[:, None, :]
        diff_t = h[:, None, :] + r[:, None, :] - neg
        diff = jnp.where(corrupt_head[..., None], diff_h, diff_t)
        negs = -jnp.sqrt(jnp.sum(diff * diff, axis=-1) + 1e-12)
    elif model == "distmult":
        pos = jnp.sum(h * r * t, axis=-1)
        s_h = jnp.sum(neg * (r * t)[:, None, :], axis=-1)
        s_t = jnp.sum((h * r)[:, None, :] * neg, axis=-1)
        negs = jnp.where(corrupt_head, s_h, s_t)
    else:
        raise ValueError(model)
    return pos, negs


# ---------------------------- swa_attention ---------------------------- #
def swa_attention_ref(
    q: jnp.ndarray,      # (B, Hq, Sq, d)
    k: jnp.ndarray,      # (B, Hkv, Skv, d)
    v: jnp.ndarray,      # (B, Hkv, Skv, d)
    window: int,         # attend to positions in (pos - window, pos]
    q_offset: int = 0,   # absolute position of q[..., 0, :] (decode: Skv-Sq)
) -> jnp.ndarray:
    """Causal sliding-window GQA attention, fp32 softmax. (B, Hq, Sq, d)."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    g = hq // hkv
    qf = q.astype(jnp.float32) * (d ** -0.5)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qf = qf.reshape(b, hkv, g, sq, d)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qf, kf)
    q_pos = q_offset + jnp.arange(sq)[:, None]
    k_pos = jnp.arange(skv)[None, :]
    causal = k_pos <= q_pos
    in_window = k_pos > q_pos - window
    mask = causal & in_window
    logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, vf)
    return out.reshape(b, hq, sq, d).astype(q.dtype)
