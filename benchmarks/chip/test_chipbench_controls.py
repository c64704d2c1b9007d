"""The controls of each cell's check, at a size a test run holds: the
reference computed one precision below the configuration's, in the
program's place, must come out not correct against the cell's limits,
and so must a training step that leaves half of its batch out."""
from __future__ import annotations

import numpy as np
import pytest

from benchmarks.chip import checks, reference, spec


def test_topk_control_fails_the_serving_limits():
    rng = np.random.default_rng(5)
    n, d = 2000, 200
    bound = 6.0 / np.sqrt(d)
    table = rng.uniform(-bound, bound, (n, d)).astype(np.float32)
    ids = [f"GO:{i:07d}" for i in range(n)]
    row_of = {e: i for i, e in enumerate(ids)}
    rows = rng.choice(n, 256, replace=False)
    ref = reference.TopKReference(table)
    ctl = reference.topk_control(table, rows, 10, ids, ids)
    numbers = reference.compare_topk(ref, rows, ctl, row_of, ids, 10)
    numbers["failed"] = 0.0
    for cell in ("go40k-topk-zipf", "go40k-topk-uniform",
                 "go40k-topk-uniform-4chip"):
        limits = spec.workload(cell)["limits"]
        assert not checks.judge(numbers, limits)["correct"], numbers


@pytest.fixture(scope="module")
def small_graph():
    from repro.ontology import synthetic
    kg = synthetic.generate(synthetic.GO_SPEC, seed=3, n_terms=3000)
    return np.asarray(kg.triples), kg.num_entities, kg.num_relations


@pytest.mark.parametrize("which", ["bfloat16", "half_batch"])
def test_training_controls_fail_the_training_limits(small_graph, which):
    triples, n_ent, n_rel = small_graph
    cfg = spec.config("go40k-transe")
    tc = dict(cfg["train"])
    seeds = [11, 12, 13]
    params0 = reference.transe_init(7, n_ent, n_rel, int(cfg["dim"]))
    ref = reference.transe_reference(params0, triples, seeds, n_ent, tc)
    if which == "bfloat16":
        bad = reference.transe_reference(params0, triples, seeds, n_ent, tc,
                                         rnd=reference.bfloat16)
    else:
        bad = reference.transe_reference(
            params0, triples, seeds, n_ent,
            {**tc, "batch_size": tc["batch_size"] // 2})
    numbers = checks.compare_training(bad, ref, params0)
    limits = spec.workload("go40k-train-transe")["limits"]
    assert not checks.judge(numbers, limits)["correct"], numbers
    same = checks.compare_training(ref, ref, params0)
    assert checks.judge(same, limits)["correct"]
