"""Rehearse cells on the CPU at a tiny size, with the look for a chip
skipped: the tests' fixtures, and the four-device rehearsal of the
sharded cell, which needs a process of its own.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python3 -m benchmarks.chip.rehearsal <tmpdir> <cell> [<fault>]

``build_tree`` writes a checkout-shaped directory holding the real metric
readers and tiny copies of the cells (the real mixes and configurations
with their sizes cut) and points ``spec`` at it.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path
from typing import Any, Dict

HERE = Path(__file__).resolve().parent
for _p in (HERE.parents[1] / "src", HERE.parents[1]):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

#: tiny cell -> (configuration, mix, chips, workload overrides)
TINY = {
    "serve-zipf": ("go40k-transe", "zipf-topk", 1, {"rate_per_s": 60}),
    "serve-uniform": ("go40k-transe", "uniform-topk", 1, {"rate_per_s": 60}),
    "serve-sharded": ("go40k-transe", "uniform-topk-sharded", 4,
                      {"rate_per_s": 60}),
    "train": ("go40k-transe", "fit-chunks", 1, {}),
}


def _write(path: Path, obj: Any) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj))


def build_tree(root: Path) -> Path:
    """Write the tiny tree under ``root``; returns its benchmark dir."""
    from benchmarks.chip import spec

    here = root / "benchmarks" / "chip"
    shutil.copytree(HERE / "metrics", here / "metrics", dirs_exist_ok=True)
    peaks = json.loads((HERE / "peaks.json").read_text())
    peaks["devices"].append({**peaks["devices"][0], "name": "rehearsal",
                             "device_kinds": ["cpu"]})
    _write(here / "peaks.json", peaks)
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = []
    limits = _limits()
    for name, (config, mix, chips, extra) in TINY.items():
        cfg = json.loads((HERE / "configs" / f"{config}.json").read_text())
        cfg.update(n_terms=300, dim=16)
        _write(here / "configs" / f"{config}.json", cfg)
        m = json.loads((HERE / "traffic" / f"{mix}.json").read_text())
        m.update(client_processes=1, client_threads=3, check_sample=40,
                 warm_cache_requests=min(64, m.get("warm_cache_requests", 0)),
                 chunk_steps=3, trace_seconds=0.5)
        _write(here / "traffic" / f"{mix}.json", m)
        _write(here / "workloads" / f"{name}.json",
               {"config": config, "traffic": mix, "chips": chips,
                "limits": limits[m["kind"]], **extra})
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": mix, "chips": chips,
                                   "why": "tiny"})
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if "workloads" in m:
                train = any("train" in w for w in m["workloads"])
                m["workloads"] = (["train"] if train else
                                  ["serve-zipf", "serve-uniform",
                                   "serve-sharded"])
    _write(root / "BENCHMARK.json", bench)
    return here


def _limits() -> Dict[str, Dict[str, float]]:
    """Each kind's limits, as the real cells set them."""
    out = {}
    for path in sorted((HERE / "workloads").glob("*.json")):
        wl = json.loads(path.read_text())
        kind = json.loads((HERE / "traffic" / f"{wl['traffic']}.json")
                          .read_text())["kind"]
        out.setdefault(kind, wl["limits"])
    return out


def rehearse(cell: str, seed: int = 12345678901, seconds: float = 1.0,
             trace: int = 0) -> Dict[str, Any]:
    from benchmarks.chip import run

    args = run.parse(["--workload", cell, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)])
    return run.run(args, platform="cpu")


def drop_exchange() -> None:
    """Break the sharded top-k: each shard keeps its own candidates and
    the gather between chips is left out."""
    import jax

    from repro.kernels import ops

    class _NoGather:
        def __getattr__(self, name):
            return getattr(jax.lax, name)

        @staticmethod
        def all_gather(x, axis_name, axis=0, tiled=False):
            return x

    class _Jax:
        lax = _NoGather()

        def __getattr__(self, name):
            return getattr(jax, name)

    ops.jax = _Jax()
    ops._sharded_topk_fn.cache_clear()


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    from benchmarks.chip import spec

    root = Path(argv[0])
    spec.HERE, spec.ROOT = build_tree(root), root
    if len(argv) > 2 and argv[2] == "drop_exchange":
        drop_exchange()
    print(json.dumps(rehearse(argv[1])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
