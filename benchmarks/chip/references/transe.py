"""TransE's plain reference (Bordes et al., NeurIPS 2013), as the
configuration's ``train`` group states it: the L1 score -|h + r - t|,
the margin-ranking loss over uniform head/tail corruptions, its gradient
by hand, Adam and the unit-ball constraint on entity rows, in float64 on
the host.  ``rnd=bfloat16`` rounds every intermediate to bfloat16 (the
control).  The harness finds this file by the configuration's ``model``.
"""
from __future__ import annotations

from typing import Callable, Dict, Sequence, Tuple

import numpy as np

from benchmarks.chip.reference import (adam, adam_state, first_batch,
                                       float64, negatives, signed_scatter)


def init(seed: int, n_entities: int, n_relations: int,
         dim: int) -> Dict[str, np.ndarray]:
    """Xavier-uniform entity and relation rows U(-6/sqrt(d), 6/sqrt(d)),
    relation rows scaled to unit L2 norm, drawn from ``jax.random.key(seed)``
    split into (entity, relation) keys."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def draw(key):
        ke, kr = jax.random.split(key)
        bound = 6.0 / jnp.sqrt(jnp.asarray(dim, jnp.float32))
        ent = jax.random.uniform(ke, (n_entities, dim), jnp.float32,
                                 -bound, bound)
        rel = jax.random.uniform(kr, (n_relations, dim), jnp.float32,
                                 -bound, bound)
        return ent, rel

    ent, rel = jax.device_get(draw(jax.random.key(seed)))
    rel = float64(rel)
    rel = rel / (np.linalg.norm(rel, axis=-1, keepdims=True) + 1e-12)
    return {"entity": float64(ent), "relation": rel}


def flops_per_triple(dim: int, num_negs: int) -> float:
    """Forward + backward flops of the L1 margin loss per positive triple,
    optimizer excluded.  Forward per scored triple: h + r - t (2d), |x|
    (d) and the sum (d); the hinge is O(1).  Backward is counted as twice
    the forward.  Each positive brings ``num_negs`` negatives."""
    forward = 4.0 * dim * (1 + num_negs)
    return 3.0 * forward


def step(params: Dict[str, np.ndarray], state: Dict[str, Dict],
         batch: np.ndarray, negs: Tuple, n_step: int, lr: float,
         margin: float = 1.0,
         rnd: Callable[[np.ndarray], np.ndarray] = float64
         ) -> Tuple[Dict, Dict, float, Dict[str, np.ndarray]]:
    """Step ``n_step`` (from 1): (params, adam moments, loss, gradients)."""
    ent, rel = params["entity"], params["relation"]
    h, r, t = batch[:, 0], batch[:, 1], batch[:, 2]
    nh, nr, nt = (a.reshape(-1) for a in negs)
    b, k = negs[0].shape
    x = rnd(rnd(ent[h] + rel[r]) - ent[t])                    # (B, d)
    xn = ent[nh]                                              # (B*K, d)
    xn += rel[nr]
    xn = rnd(xn)
    xn -= ent[nt]
    xn = rnd(xn)
    pos = rnd(-np.abs(x).sum(-1))
    neg = rnd(-np.abs(xn).sum(-1)).reshape(b, k)
    hinge = margin + neg - pos[:, None]
    active = (hinge > 0).astype(np.float64)
    loss = float(rnd(np.maximum(hinge, 0.0).mean()))
    d_neg = (active / (b * k)).reshape(-1, 1)                 # dL/dneg
    d_pos = -active.sum(1, keepdims=True) / (b * k)           # dL/dpos
    gx = rnd(np.sign(x) * -d_pos)                             # dL/dx
    gxn = np.sign(xn)
    gxn *= -d_neg
    gxn = rnd(gxn)
    grads = {
        "entity": rnd(signed_scatter(len(ent), h, t, gx)
                      + signed_scatter(len(ent), nh, nt, gxn)),
        "relation": rnd(signed_scatter(len(rel), r, None, gx)
                        + signed_scatter(len(rel), nr, None, gxn)),
    }
    new_params, new_state = adam(params, grads, state, n_step, lr, rnd)
    e = new_params["entity"]
    norm = np.linalg.norm(e, axis=-1, keepdims=True)
    new_params["entity"] = rnd(e / np.maximum(norm, 1.0))
    return new_params, new_state, loss, grads


def reference(params0: Dict[str, np.ndarray], triples: np.ndarray,
              step_seeds: Sequence[int], n_entities: int, train_cfg: Dict,
              rnd: Callable[[np.ndarray], np.ndarray] = float64
              ) -> Dict[str, object]:
    """Follow the program's first ``len(step_seeds)`` steps, one fresh
    epoch shuffle and key per step.  Returns each step's loss, the first
    step's gradients and the parameters after the last step.  A ``train``
    group that asks for another loss, norm or optimizer than this file
    computes is refused."""
    asked = (train_cfg["loss"], int(train_cfg["p_norm"]),
             train_cfg["optimizer"])
    if asked != ("margin", 1, "adam"):
        raise ValueError(f"the TransE reference computes the margin loss, "
                         f"p_norm 1 and Adam; the configuration asks for "
                         f"loss, p_norm, optimizer = {asked}")
    params = {k: rnd(v) for k, v in params0.items()}
    state = adam_state(params)
    losses, first_grads = [], None
    for i, seed in enumerate(step_seeds):
        batch = first_batch(triples, train_cfg["batch_size"], seed)
        negs = negatives(seed, batch, n_entities, train_cfg["num_negs"])
        params, state, loss, grads = step(
            params, state, batch, negs, i + 1, train_cfg["lr"],
            margin=train_cfg["margin"], rnd=rnd)
        losses.append(loss)
        if first_grads is None:
            first_grads = grads
    return {"losses": losses, "grads": first_grads, "params": params}
