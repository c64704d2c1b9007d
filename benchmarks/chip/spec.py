"""Find configurations, mixes, cells, metrics, model references and
peaks by name.

Nothing here imports JAX: the client fleet's processes import this
module too, and they never touch the chip.
"""
from __future__ import annotations

import importlib.util
import json
import zlib
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
#: the checkout's root: BENCHMARK.json, src/, the compile cache
ROOT = HERE.parents[1]


class SpecError(KeyError):
    """A name that has no file, or files that disagree."""


def _load(kind: str, name: str) -> Dict[str, Any]:
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no {kind[:-1]} named {name!r} ({path})")
    return json.loads(path.read_text())


def config(name: str) -> Dict[str, Any]:
    return _load("configs", name)


def traffic(name: str) -> Dict[str, Any]:
    return _load("traffic", name)


def workload(name: str) -> Dict[str, Any]:
    return _load("workloads", name)


def benchmark() -> Dict[str, Any]:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    """The cell's entry in BENCHMARK.json merged with its workload file;
    the two must name the same configuration, mix and chips."""
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SpecError(f"BENCHMARK.json has no workload {name!r}")
    wl = workload(name)
    for key in ("config", "traffic", "chips"):
        if wl.get(key) != entry[key]:
            raise SpecError(f"workloads/{name}.json says {key}="
                            f"{wl.get(key)!r}, BENCHMARK.json says "
                            f"{entry[key]!r}")
    return {**wl, "name": name}


def _listed(metric: Dict[str, Any], name: str) -> Optional[bool]:
    cells = metric.get("workloads")
    return None if cells is None else name in cells


def end_to_end(bench: Dict[str, Any], name: str) -> List[Dict[str, Any]]:
    """The cell's end-to-end metrics: those that list it, and those that
    list no cells at all."""
    return [m for m in bench["end_to_end"] if _listed(m, name) is not False]


def per_layer(bench: Dict[str, Any], name: str) -> List[Dict[str, Any]]:
    """The cell's per-layer metrics: those that list it, and unlisted
    ones whose moved metric the cell reports."""
    e2e = {m["name"] for m in end_to_end(bench, name)}
    out = []
    for m in bench["per_layer"]:
        listed = _listed(m, name)
        if listed or (listed is None and m["moves"] in e2e):
            out.append(m)
    return out


def _module(kind: str, name: str) -> ModuleType:
    """``<kind>/<name>.py``, loaded afresh from ``HERE``."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no {kind} file for {name!r} ({path})")
    mod_name = f"chipbench_{kind}_" + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    """``metrics/<name>.py``'s ``read(record)``."""
    return _module("metrics", name).read


def train_reference(model: str) -> ModuleType:
    """``references/<model>.py``, the plain reference of a configuration's
    ``model``: ``init(seed, n_entities, n_relations, dim)``,
    ``reference(params0, triples, step_seeds, n_entities, train_cfg,
    rnd=...)`` and ``flops_per_triple(dim, num_negs)``."""
    return _module("references", model)


def peaks(device_kind: str) -> Dict[str, Any]:
    """Published peaks of one chip; an unknown device is an error."""
    table = json.loads((HERE / "peaks.json").read_text())
    for row in table["devices"]:
        if device_kind in row["device_kinds"]:
            return row
    raise SpecError(f"no peaks for device_kind {device_kind!r} in "
                    f"peaks.json; add a row with its published source")


def subseed(seed: int, stream: str) -> int:
    """A 31-bit seed for one named stream of a run's ``--seed``; any whole
    number is accepted, including ones wider than 32 bits."""
    s = int(seed)
    words = [abs(s) & 0xFFFFFFFF, (abs(s) >> 32) & 0xFFFFFFFF,
             1 if s < 0 else 0, zlib.crc32(stream.encode())]
    return int(np.random.SeedSequence(words).generate_state(1)[0]) & 0x7FFFFFFF
