"""Training launcher.

Two modes, matching the paper's two sides:

  KGE (the paper's workload):
      PYTHONPATH=src python -m repro.launch.train --workload go \
          --registry /tmp/biokg --steps 200
    Generates the synthetic GO/HP release, trains all six KGE models
    (paper defaults: dim=200, epochs=100 — cap with --steps on CPU), and
    publishes versioned snapshots with PROV metadata.

  LM zoo (assigned architectures; reduced configs on CPU):
      PYTHONPATH=src python -m repro.launch.train --arch qwen2-72b \
          --reduced --steps 50
    Runs real optimizer steps on synthetic token streams and reports the
    loss curve. On TPU the same driver takes the full config + the
    production mesh (see launch/dryrun.py for the lowering path).
"""
from __future__ import annotations

import argparse
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np


def train_kge(workload: str, registry_dir: str, steps: int | None,
              n_terms: int | None, seed: int = 0) -> None:
    from repro.core.registry import EmbeddingRegistry
    from repro.core.updater import Updater
    from repro.ontology.synthetic import generate
    from repro.ontology import obo

    wl_mod = importlib.import_module(f"repro.configs.{workload}_kge")
    wl = wl_mod.CONFIG if n_terms is None else wl_mod.REDUCED
    n = n_terms or wl.n_terms

    print(f"[train] generating synthetic {workload.upper()} ({n} terms)")
    kg = generate(wl.spec, seed=seed, n_terms=n)
    print(f"[train] {kg.num_entities} entities, {len(kg.triples)} triples, "
          f"{kg.num_relations} relations")

    registry = EmbeddingRegistry(registry_dir)
    updater = Updater(registry, models=wl.models, dim=wl.dim,
                      train_cfg=wl.train, steps_override=steps)

    class _Once:
        name = workload
        def latest(self):
            return "2023-01-01", kg

    rep = updater.run_once(_Once(), seed=seed)
    print(f"[train] published {rep.trained_models} v{rep.version} "
          f"in {rep.wall_s:.1f}s")
    for m, d in rep.details.items():
        print(f"  {m:10s} loss={d['final_loss']:.4f} "
              f"{d['triples_per_s']:.0f} triples/s")


def train_lm(arch: str, reduced: bool, steps: int, batch: int, seq: int,
             seed: int = 0) -> None:
    from repro.models import get_model
    from repro.models.steps import make_train_step

    cfg, model = get_model(arch, reduced=reduced)
    print(f"[train] {cfg.arch_id}: {cfg.n_params()/1e6:.1f}M params "
          f"({cfg.n_active_params()/1e6:.1f}M active)")
    key = jax.random.key(seed)
    params = model.init(key)
    step, optimizer = make_train_step(model)
    opt_state = optimizer.init(params)
    jstep = jax.jit(step, donate_argnums=(0, 1))

    rng = np.random.default_rng(seed)
    spec = model.batch_spec(batch, seq)

    def make_batch():
        out = {}
        for k, v in spec.items():
            if v.dtype == jnp.int32:
                out[k] = jnp.asarray(
                    rng.integers(0, cfg.vocab, v.shape), jnp.int32)
            else:
                out[k] = jnp.asarray(rng.normal(size=v.shape), v.dtype)
        return out

    t0 = time.perf_counter()
    for i in range(steps):
        params, opt_state, metrics = jstep(params, opt_state, make_batch())
        if i % max(1, steps // 10) == 0 or i == steps - 1:
            print(f"  step {i:4d} loss={float(metrics['loss']):.4f} "
                  f"acc={float(metrics['acc']):.3f}")
    dt = time.perf_counter() - t0
    tok = steps * batch * seq
    print(f"[train] {steps} steps, {dt:.1f}s, {tok/dt:.0f} tok/s")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", choices=["go", "hp"], default=None)
    ap.add_argument("--registry", default="/tmp/biokgvec2go")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--n-terms", type=int, default=None)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from .compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.workload:
        train_kge(args.workload, args.registry, args.steps, args.n_terms,
                  args.seed)
    elif args.arch:
        train_lm(args.arch, args.reduced, args.steps or 20, args.batch,
                 args.seq, args.seed)
    else:
        raise SystemExit("pass --workload go|hp or --arch <id>")


if __name__ == "__main__":
    main()
