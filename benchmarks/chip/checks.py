"""The numbers that decide ``correct``, each held against its limit.

The limits live in each cell's workload file (``limits``), set from the
readings that ``PERF.md`` lists: the program's largest over a dozen
seeds or more, and the control's smallest.  A number must not exceed
its limit; a number without a limit, or a limit without a number, is an
error of the harness and fails the run.
"""
from __future__ import annotations

import sys
from typing import Dict, Mapping, Optional

import numpy as np


def judge(numbers: Mapping[str, float], limits: Mapping[str, float]
          ) -> Dict[str, object]:
    """``{"correct": bool, "checks": {name: {"value", "limit"}}}``."""
    checks = {}
    ok = set(numbers) == set(limits)
    for name in sorted(set(numbers) | set(limits)):
        value = numbers.get(name)
        limit = limits.get(name)
        checks[name] = {"value": value, "limit": limit}
        if value is None or limit is None or not np.isfinite(value) \
                or value > limit:
            ok = False
    return {"correct": bool(ok), "checks": checks}


def print_checks(checks: Mapping[str, Mapping[str, Optional[float]]]) -> None:
    """The compared numbers, beside their limits, as the last lines on
    standard error."""
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)


def _leaf_gaps(prog: Mapping[str, np.ndarray], ref: Mapping[str, np.ndarray],
               keep: Mapping[str, bool]) -> float:
    """Worst leaf's |norm(program) - norm(reference)| against the larger
    of that leaf's reference norm and the median leaf's."""
    ref_n = {k: float(np.linalg.norm(ref[k])) for k in ref}
    med = float(np.median(list(ref_n.values())))
    worst = 0.0
    for k in ref:
        if not keep[k]:
            continue
        gap = abs(float(np.linalg.norm(prog[k])) - ref_n[k])
        worst = max(worst, gap / max(ref_n[k], med, 1e-30))
    return worst


def compare_training(prog: Mapping[str, object], ref: Mapping[str, object],
                     params0: Mapping[str, np.ndarray]) -> Dict[str, float]:
    """``loss_gap``: the widest relative gap of a step's loss;
    ``grad_gap``: the first gradient's norm, worst leaf; ``update_gap``:
    the norm of the parameters' change over the steps, worst leaf.

    Leaves whose reference gradient is under a thousandth of the median
    leaf's move under Adam by round-off alone, and are left out of both
    leaf numbers."""
    losses = [abs(p - r) / max(abs(r), 1e-30)
              for p, r in zip(prog["losses"], ref["losses"])]
    g_norm = {k: float(np.linalg.norm(v)) for k, v in ref["grads"].items()}
    med = float(np.median(list(g_norm.values())))
    keep = {k: g_norm[k] >= 1e-3 * med for k in g_norm}
    d_prog = {k: np.asarray(prog["params"][k], np.float64) - params0[k]
              for k in params0}
    d_ref = {k: ref["params"][k] - params0[k] for k in params0}
    return {
        "loss_gap": max(losses) if len(losses) == len(ref["losses"])
        else float("inf"),
        "grad_gap": _leaf_gaps(prog["grads"], ref["grads"], keep),
        "update_gap": _leaf_gaps(d_prog, d_ref, keep),
    }
