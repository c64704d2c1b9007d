"""Plain references: cosine top-k, and the parts of KGE training that
no model changes.

Nothing here imports the program.  The references take the benchmark's
own inputs (the seeded table, the seeded ontology's triples) and rebuild
everything else themselves:

* cosine top-k: a float64 brute force over the raw table and its norms,
  each query's own row excluded, ties to the lower row;
* KGE training, shared by every model's ``references/<model>.py``: the
  shuffled first batch of an epoch and the uniform head/tail negatives,
  redrawn from the same seeds with NumPy and ``jax.random`` the way the
  published algorithm draws them; a signed scatter of row gradients; and
  Adam, in float64 on the host.  A model's file adds its score, loss,
  gradient by hand and constraint.

The controls compute the same in the precision below the configuration's
float32: ``topk_control`` scores with three bfloat16 passes, and a
model's ``reference(..., rnd=bfloat16)`` rounds every intermediate to
bfloat16.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

#: rows of queries scored at once against the whole table (memory bound)
QUERY_BLOCK = 128


# ------------------------------ top-k ---------------------------------- #
class TopKReference:
    """Float64 cosine scores over a raw (N, d) table and its norms."""

    def __init__(self, table: np.ndarray):
        t = np.asarray(table, np.float64)
        norms = np.linalg.norm(np.asarray(table, np.float32),
                               axis=1).astype(np.float64)
        self.unit = t / np.maximum(norms, 1e-12)[:, None]

    def scores(self, rows: np.ndarray) -> np.ndarray:
        """(Q, N) scores, each query's own row at -inf."""
        rows = np.asarray(rows)
        s = self.unit[rows] @ self.unit.T
        s[np.arange(len(rows)), rows] = -np.inf
        return s


def compare_topk(ref: TopKReference, query_rows: Sequence[int],
                 answers: Sequence[Sequence[Tuple[str, str, float]]],
                 row_of: Dict[str, int], labels: Sequence[str],
                 k: int) -> Dict[str, float]:
    """Compare served answers (identifier, label, score) with the
    reference.  ``score_gap``: the widest of |served score - reference
    score of the served row| and of the amount by which the served row at
    rank j scores below the reference's j-th best, so a wrong score and a
    wrong neighbour both show; ``bad_answers``: answers of the wrong
    length, with a duplicate, the query itself, an unknown identifier or
    a wrong label."""
    n = ref.unit.shape[0]
    want = min(k, n - 1)
    gap = 0.0
    bad = 0
    rows = np.asarray(query_rows)
    for start in range(0, len(rows), QUERY_BLOCK):
        blk = rows[start:start + QUERY_BLOCK]
        s = ref.scores(blk)
        best = -np.sort(-s, axis=1)[:, :want]
        for qi, qrow in enumerate(blk):
            got = answers[start + qi]
            got_rows = [row_of.get(ident, -1) for ident, _, _ in got]
            if (len(got) != want or len(set(got_rows)) != len(got_rows)
                    or int(qrow) in got_rows or -1 in got_rows
                    or any(labels[r] != lbl
                           for r, (_, lbl, _) in zip(got_rows, got))):
                bad += 1
                continue
            true = s[qi, got_rows]
            served = np.array([sc for _, _, sc in got], np.float64)
            gap = max(gap, float(np.max(np.abs(served - true))),
                      float(np.max(best[qi] - true)))
    return {"score_gap": gap, "bad_answers": float(bad)}


def topk_control(table: np.ndarray, query_rows: Sequence[int], k: int,
                 ids: Sequence[str], labels: Sequence[str]
                 ) -> List[List[Tuple[str, str, float]]]:
    """The reference in the program's place, one precision below the
    configuration's float32 at ``HIGHEST``: cosine top-k on the default
    device whose scoring dot takes three bfloat16 passes, the
    ``Precision.HIGH`` algorithm, written out so that it computes the
    same on any backend.  The unit rows split on the host into a bfloat16
    head and tail; head*head + head*tail + tail*head are float32 dots of
    bfloat16 values (exact products), so no compiler can drop the split
    as excess precision."""
    import jax
    import jax.numpy as jnp
    import ml_dtypes

    def split(x):
        hi = x.astype(ml_dtypes.bfloat16).astype(np.float32)
        lo = (x - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
        return jnp.asarray(hi), jnp.asarray(lo)

    def dot(a, b):
        return jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)

    @jax.jit
    def run(qh, ql, uh, ul, q_rows):
        s = dot(qh, uh) + dot(qh, ul) + dot(ql, uh)
        s = s.at[jnp.arange(q_rows.shape[0]), q_rows].set(-jnp.inf)
        return jax.lax.top_k(s, k)

    t = np.asarray(table, np.float32)
    unit = t / np.maximum(np.linalg.norm(t, axis=1), 1e-12)[:, None]
    uh, ul = split(unit)
    out = []
    rows = np.asarray(query_rows, np.int32)
    for start in range(0, len(rows), QUERY_BLOCK):
        blk = rows[start:start + QUERY_BLOCK]
        q = np.concatenate([blk, np.repeat(blk[-1:], QUERY_BLOCK - len(blk))])
        qh, ql = split(unit[q])
        sc, ix = jax.device_get(run(qh, ql, uh, ul, jnp.asarray(q)))
        for qi in range(len(blk)):
            out.append([(ids[j], labels[j], float(v))
                        for v, j in zip(sc[qi], ix[qi])])
    return out


# --------------------------- KGE training ------------------------------ #
def float64(x: np.ndarray) -> np.ndarray:
    return np.asarray(x, np.float64)


def bfloat16(x: np.ndarray) -> np.ndarray:
    """Round to bfloat16 and back (the control's precision)."""
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float64)


def first_batch(triples: np.ndarray, batch: int, seed: int) -> np.ndarray:
    """The first batch of an epoch shuffled by ``default_rng(seed)``; a
    graph with fewer triples than a batch repeats its shuffle to fill it."""
    perm = np.random.default_rng(seed).permutation(len(triples))
    shuf = np.asarray(triples, np.int64)[perm]
    reps = -(-batch // len(shuf))
    return np.tile(shuf, (reps, 1))[:batch]


@functools.lru_cache(maxsize=8)
def _negatives_fn(b: int, num_negs: int, n_entities: int):
    import jax

    @jax.jit
    def draw(key):
        _, sub = jax.random.split(key)
        k_rand, k_side = jax.random.split(sub)
        ents = jax.random.randint(k_rand, (b, num_negs), 0, n_entities)
        side = jax.random.bernoulli(k_side, 0.5, (b, num_negs))
        return ents, side

    return draw


def negatives(seed: int, batch: np.ndarray, n_entities: int,
              num_negs: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(heads, relations, tails) of the (B, K) corrupted triples of the
    first step keyed by ``seed``: the step key is the second half of
    ``split(key(seed + 1))``; it splits into (entities, sides); each
    negative replaces the head (side true) or the tail with a uniform
    entity."""
    import jax

    draw = _negatives_fn(batch.shape[0], num_negs, n_entities)
    ents, side = jax.device_get(draw(jax.random.key(seed + 1)))
    ents = np.asarray(ents, np.int64)
    h = np.where(side, ents, batch[:, 0:1])
    t = np.where(side, batch[:, 2:3], ents)
    r = np.broadcast_to(batch[:, 1:2], h.shape)
    return h, r, t


def signed_scatter(n_rows: int, plus: np.ndarray, minus, vals: np.ndarray
                   ) -> np.ndarray:
    """(n_rows, d): each row of ``vals`` added at ``plus`` and, where
    ``minus`` is given, subtracted at ``minus``."""
    import scipy.sparse as sp

    m = len(plus)
    cols = np.arange(m)
    if minus is None:
        data, rows, cols = np.ones(m), plus, cols
    else:
        data = np.r_[np.ones(m), -np.ones(m)]
        rows, cols = np.r_[plus, minus], np.r_[cols, cols]
    return sp.csr_matrix((data, (rows, cols)), shape=(n_rows, m)) @ vals


def adam_state(params: Dict[str, np.ndarray]) -> Dict[str, Dict]:
    """Adam's first and second moments of every leaf, at zero."""
    return {k: {"mu": np.zeros_like(v), "nu": np.zeros_like(v)}
            for k, v in params.items()}


def adam(params: Dict[str, np.ndarray], grads: Dict[str, np.ndarray],
         state: Dict[str, Dict], step: int, lr: float,
         rnd: Callable[[np.ndarray], np.ndarray] = float64,
         b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8
         ) -> Tuple[Dict[str, np.ndarray], Dict[str, Dict]]:
    """One bias-corrected Adam update of every leaf at ``step`` (from 1),
    each moment and the update rounded by ``rnd``: (params, state)."""
    new_params, new_state = {}, {}
    for name, p in params.items():
        g = grads[name]
        m = state[name]["mu"] * b1
        m += (1 - b1) * g
        m = rnd(m)
        v = state[name]["nu"] * b2
        gg = g * g
        gg *= 1 - b2
        v += gg
        v = rnd(v)
        upd = v / (1 - b2 ** step)                  # v_hat
        np.sqrt(upd, out=upd)
        upd += eps
        np.divide(m, upd, out=upd)
        upd *= 1.0 / (1 - b1 ** step)               # m_hat / (sqrt + eps)
        upd = rnd(upd)
        upd *= -lr
        upd += p
        new_params[name] = rnd(upd)
        new_state[name] = {"mu": m, "nu": v}
    return new_params, new_state
