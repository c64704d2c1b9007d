"""Training cells: KGE steps through ``KGETrainer.fit``, as the updater
runs them.

Set-up builds one trainer (the compiled step) and its state from the
seed and drives it through the first ``check_steps`` steps, each a
``fit(steps=1)`` call on a fresh epoch shuffle and key; those steps are
what the reference follows.  The same trainer and state then run the
window: ``fit`` in chunks of ``chunk_steps``, each chunk on its own seed,
until the window's seconds are spent.  ``fit`` reads the last loss of
every chunk back to the host, so each chunk ends with the device done.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional

import numpy as np

from .spec import subseed


class Training:
    def __init__(self, cfg: Dict[str, Any], mix: Dict[str, Any], seed: int):
        from repro.kge import KGETrainer, TrainConfig, make_model
        from repro.ontology import synthetic

        tr = cfg["train"]
        kg = synthetic.generate(getattr(synthetic, cfg["profile"]),
                                seed=subseed(seed, "ontology"),
                                n_terms=int(cfg["n_terms"]))
        self.triples = np.asarray(kg.triples)
        self.n_entities = kg.num_entities
        self.n_relations = kg.num_relations
        model = make_model(cfg["model"], kg.num_entities, kg.num_relations,
                           dim=int(cfg["dim"]), loss=tr["loss"],
                           margin=float(tr["margin"]),
                           p_norm=int(tr["p_norm"]))
        self.base = TrainConfig(batch_size=int(tr["batch_size"]),
                                num_negs=int(tr["num_negs"]),
                                optimizer=tr["optimizer"], lr=float(tr["lr"]))
        self.trainer = KGETrainer(model, self.base)
        self.init_seed = subseed(seed, "init")
        self.params, self.opt = self.trainer.init(self.init_seed)
        self.seed = seed
        self.chunk = int(mix["chunk_steps"])
        self.step_seeds = [subseed(seed, f"step{i}")
                           for i in range(int(mix["check_steps"]))]

    def _fit(self, seed: int, steps: int) -> Dict[str, Any]:
        self.trainer.cfg = dataclasses.replace(self.base, seed=seed)
        self.params, self.opt, stats = self.trainer.fit(
            self.triples, params=self.params, opt_state=self.opt,
            steps=steps)
        return stats

    def first_steps(self) -> Dict[str, Any]:
        """The checked steps: each step's loss, the first gradient as the
        optimizer holds it after one step (Adam's first moment over
        1 - beta1), and the parameters after the last."""
        import jax

        losses, grads = [], None
        for s in self.step_seeds:
            losses.append(self._fit(s, 1)["final_loss"])
            if grads is None:
                b1 = 0.9
                grads = {k: np.asarray(jax.device_get(v), np.float64)
                         / (1 - b1) for k, v in self.opt.mu.items()}
        params = {k: np.asarray(jax.device_get(v))
                  for k, v in self.params.items()}
        return {"losses": losses, "grads": grads, "params": params}

    def run(self, seconds: float, chunk_seed: Callable[[int], int],
            span: Optional[Callable] = None) -> Dict[str, float]:
        """Chunks until ``seconds`` have passed; (steps, elapsed)."""
        import contextlib

        import jax

        ctx = span("chipbench.window") if span else contextlib.nullcontext()
        steps, c = 0, 0
        with ctx:
            t0 = time.perf_counter()
            while True:
                self._fit(chunk_seed(c), self.chunk)
                steps += self.chunk
                c += 1
                if time.perf_counter() - t0 >= seconds:
                    break
            jax.block_until_ready(self.params)
            elapsed = time.perf_counter() - t0
        return {"steps": steps, "elapsed_s": elapsed,
                "triples": steps * self.base.batch_size}

    def free(self) -> None:
        self.params = self.opt = self.trainer = None


def window(tr: Training, seconds: float, seed: int) -> Dict[str, float]:
    return tr.run(seconds, lambda c: subseed(seed, f"chunk{c}"))


def traced(tr: Training, seconds: float, seed: int,
           trace_dir: str) -> Dict[str, float]:
    """A further stretch of chunks under the profiler."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        return tr.run(seconds, lambda c: subseed(seed, f"trace{c}"),
                      span=jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
