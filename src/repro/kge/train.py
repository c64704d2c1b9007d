"""Sharded KGE trainer.

One jit'd step: gather batch rows from the (possibly model-axis vocab-
sharded) tables, corrupt negatives, score with the model, apply the model's
loss, Adam/Adagrad update, post-step constraint. Under a mesh, the entity
table lives as P("model", None) and the batch as P("data"); XLA inserts the
gather/reduce-scatter collectives — no hand-written NCCL-style code.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..data.triples import TripleLoader
from ..optim import OPTIMIZERS, Optimizer
from .base import KGEModel, Params, remap_params
from .losses import get_loss
from .negatives import corrupt


@dataclasses.dataclass
class TrainConfig:
    batch_size: int = 1024
    num_negs: int = 32
    epochs: int = 100                  # paper default
    optimizer: str = "adam"
    lr: float = 1e-2
    reg_weight: float = 0.0
    seed: int = 0
    log_every: int = 50


def make_train_step(model: KGEModel, optimizer: Optimizer, cfg: TrainConfig):
    loss_fn = get_loss(model.spec.loss)
    loss_kwargs: Dict[str, Any] = {}
    if model.spec.loss in ("margin", "nssa"):
        loss_kwargs["margin"] = model.spec.margin

    def loss_of(params: Params, triples: jnp.ndarray, key: jax.Array):
        pos = model.score(params, triples[:, 0], triples[:, 1], triples[:, 2])
        nh, nr, nt = corrupt(key, triples, model.spec.n_entities, cfg.num_negs)
        neg = model.score(params, nh, nr, nt)
        loss = loss_fn(pos, neg, **loss_kwargs)
        if cfg.reg_weight:
            loss = loss + cfg.reg_weight * model.regularizer(
                params, triples[:, 0], triples[:, 1], triples[:, 2]
            )
        return loss

    def step(params: Params, opt_state, triples: jnp.ndarray, key: jax.Array):
        loss, grads = jax.value_and_grad(loss_of)(params, triples, key)
        params, opt_state = optimizer.update(grads, opt_state, params)
        params = model.constrain(params)
        return params, opt_state, loss

    return step, loss_of


class KGETrainer:
    """Drives the jit'd step over a TripleLoader; optionally mesh-sharded."""

    def __init__(self, model: KGEModel, cfg: TrainConfig, mesh: Optional[Mesh] = None):
        self.model = model
        self.cfg = cfg
        self.mesh = mesh
        self.optimizer = OPTIMIZERS[cfg.optimizer](cfg.lr)
        step, self._loss_of = make_train_step(model, self.optimizer, cfg)

        if mesh is not None:
            pspec = model.param_shardings("model", axis_size=mesh.shape.get("model"))
            param_sh = {k: NamedSharding(mesh, v) for k, v in pspec.items()}
            batch_sh = NamedSharding(mesh, P("data", None))
            rep = NamedSharding(mesh, P())
            self._step = jax.jit(
                step,
                in_shardings=(param_sh, None, batch_sh, rep),
                out_shardings=(param_sh, None, rep),
                donate_argnums=(0, 1),
            )
            self._param_sh = param_sh
        else:
            self._step = jax.jit(step, donate_argnums=(0, 1))
            self._param_sh = None

    def init(self, seed: Optional[int] = None) -> Tuple[Params, Any]:
        key = jax.random.key(self.cfg.seed if seed is None else seed)
        params = self.model.init(key)
        if self._param_sh is not None:
            params = jax.device_put(params, self._param_sh)
        return params, self.optimizer.init(params)

    def warm_init(
        self,
        prev_params: Params,
        entity_map: np.ndarray,
        relation_map: np.ndarray,
        seed: Optional[int] = None,
    ) -> Tuple[Params, Any, Dict[str, int]]:
        """Init from a previous version's params remapped onto this model's
        vocabulary (see :func:`repro.kge.base.remap_params`): surviving rows
        carried, new rows fresh, removed rows dropped. Optimizer state is
        fresh — the old moments index the old row space.

        Returns (params, opt_state, carry_stats).
        """
        key = jax.random.key(self.cfg.seed if seed is None else seed)
        params, stats = remap_params(self.model, key, prev_params,
                                     entity_map, relation_map)
        if self._param_sh is not None:
            params = jax.device_put(params, self._param_sh)
        return params, self.optimizer.init(params), stats

    def fit(
        self,
        triples: np.ndarray,
        params: Optional[Params] = None,
        opt_state: Any = None,
        epochs: Optional[int] = None,
        steps: Optional[int] = None,
        log: Optional[Callable[[Dict[str, Any]], None]] = None,
    ) -> Tuple[Params, Any, Dict[str, Any]]:
        """Train for ``epochs`` (paper default 100) or an explicit ``steps``."""
        # a function-level import: the repro.core package imports the
        # updater, which imports this module
        from ..core.metrics import span

        cfg = self.cfg
        if params is None:
            params, opt_state = self.init()
        loader = TripleLoader(triples, cfg.batch_size, seed=cfg.seed)
        n_epochs = cfg.epochs if epochs is None else epochs
        total_steps = steps if steps is not None else n_epochs * max(1, loader.steps_per_epoch)

        key = jax.random.key(cfg.seed + 1)
        it = iter(loader)
        losses = []
        t0 = time.perf_counter()
        for i in range(total_steps):
            # trace-only spans: no counter, one annotation each under a
            # profiler and two cheap calls without one
            with span("train.batch"):
                key, sub = jax.random.split(key)
                batch = jnp.asarray(next(it))
            with span("train.step"):
                params, opt_state, loss = self._step(params, opt_state,
                                                     batch, sub)
            if i % cfg.log_every == 0 or i == total_steps - 1:
                l = float(loss)
                losses.append((i, l))
                if log:
                    log({"step": i, "loss": l})
        elapsed = time.perf_counter() - t0
        stats = {
            "steps": total_steps,
            "final_loss": losses[-1][1] if losses else float("nan"),
            "losses": losses,
            "wall_s": elapsed,
            "triples_per_s": total_steps * cfg.batch_size / max(elapsed, 1e-9),
        }
        return params, opt_state, stats
