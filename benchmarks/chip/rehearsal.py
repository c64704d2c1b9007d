"""Rehearse cells on the CPU at a tiny size, with the look for a chip
skipped: the tests' fixtures, and the four-device rehearsal of a
four-chip cell, which needs a process of its own.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python3 -m benchmarks.chip.rehearsal <tmpdir> <cell> [<fault>]

``build_tree`` writes a checkout-shaped directory holding the real metric
readers and model references and a tiny copy of every cell of
BENCHMARK.json under its own name: its own mix, configuration and limits,
with the sizes cut.  ``main`` points ``spec`` at it.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent
for _p in (HERE.parents[1] / "src", HERE.parents[1]):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

#: the rate of every tiny serving cell, in requests per second
TINY_RATE = 60


def cells(chips: int, kind: Optional[str] = None) -> List[str]:
    """BENCHMARK.json's cells on ``chips`` chips, of one traffic ``kind``
    or of any."""
    from benchmarks.chip import spec

    bench = spec.benchmark()
    return [w["name"] for w in bench["workloads"] if w["chips"] == chips
            and kind in (None, spec.traffic(w["traffic"])["kind"])]


def _write(path: Path, obj: Any) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj))


def _read(kind: str, name: str) -> Dict[str, Any]:
    return json.loads((HERE / kind / f"{name}.json").read_text())


def build_tree(root: Path) -> Path:
    """Write a tiny copy of every cell of BENCHMARK.json under ``root``,
    each under its own name and with its own limits; returns the tree's
    benchmark dir."""
    from benchmarks.chip import spec

    here = root / "benchmarks" / "chip"
    for kind in ("metrics", "references"):
        shutil.copytree(HERE / kind, here / kind, dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    peaks = json.loads((HERE / "peaks.json").read_text())
    peaks["devices"].append({**peaks["devices"][0], "name": "rehearsal",
                             "device_kinds": ["cpu"]})
    _write(here / "peaks.json", peaks)
    shutil.copy(spec.ROOT / "BENCHMARK.json", root)
    for w in spec.benchmark()["workloads"]:
        cfg = _read("configs", w["config"])
        cfg.update(n_terms=300, dim=16)
        _write(here / "configs" / f"{w['config']}.json", cfg)
        m = _read("traffic", w["traffic"])
        m.update(client_processes=1, client_threads=3, check_sample=40,
                 warm_cache_requests=min(64, m.get("warm_cache_requests", 0)),
                 chunk_steps=3, trace_seconds=0.5)
        _write(here / "traffic" / f"{w['traffic']}.json", m)
        wl = _read("workloads", w["name"])
        if "rate_per_s" in wl:
            wl["rate_per_s"] = TINY_RATE
        _write(here / "workloads" / f"{w['name']}.json", wl)
    return here


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
