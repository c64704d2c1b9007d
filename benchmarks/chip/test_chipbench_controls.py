"""The controls of each cell's check, at a size a test run holds: the
reference computed one precision below the configuration's, in the
program's place, must come out not correct against the cell's limits,
and so must a training step that leaves half of its batch out.  The
training reference is the one its configuration's ``model`` names; the
TransE file reproduces, bit for bit, the values it gave before it moved
out of ``reference.py``."""
from __future__ import annotations

import hashlib

import numpy as np
import pytest

from benchmarks.chip import checks, reference, spec
from benchmarks.chip.rehearsal import cells


@pytest.mark.parametrize("cell",
                         cells(1, "topk_http") + cells(4, "topk_http"))
def test_topk_control_fails_the_serving_limits(cell):
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
    limits = spec.workload(cell)["limits"]
    assert not checks.judge(numbers, limits)["correct"], numbers


@pytest.fixture(scope="module")
def small_graph():
    from repro.ontology import synthetic
    kg = synthetic.generate(synthetic.GO_SPEC, seed=3, n_terms=3000)
    return np.asarray(kg.triples), kg.num_entities, kg.num_relations


@pytest.mark.parametrize("cell", cells(1, "kge_train"))
@pytest.mark.parametrize("which", ["bfloat16", "half_batch"])
def test_training_controls_fail_the_training_limits(small_graph, which, cell):
    triples, n_ent, n_rel = small_graph
    cfg = spec.config(spec.workload(cell)["config"])
    model = spec.train_reference(cfg["model"])
    tc = dict(cfg["train"])
    seeds = [11, 12, 13]
    params0 = model.init(7, n_ent, n_rel, int(cfg["dim"]))
    ref = model.reference(params0, triples, seeds, n_ent, tc)
    if which == "bfloat16":
        bad = model.reference(params0, triples, seeds, n_ent, tc,
                              rnd=reference.bfloat16)
    else:
        bad = model.reference(params0, triples, seeds, n_ent,
                              {**tc, "batch_size": tc["batch_size"] // 2})
    numbers = checks.compare_training(bad, ref, params0)
    limits = spec.workload(cell)["limits"]
    assert not checks.judge(numbers, limits)["correct"], numbers
    same = checks.compare_training(ref, ref, params0)
    assert checks.judge(same, limits)["correct"]


#: TransE's reference on ``small_graph``, go40k-transe's ``train`` group
#: at d=200, init seed 7 and step seeds 11-13, as ``reference.py`` gave
#: them before the move: each step's loss, the first step's gradient
#: norms, the final parameter norms (summed in NumPy's pairwise order,
#: not by BLAS) and the SHA-256 of the raw float64 leaves, sorted by name
GOLDEN = {
    "float64": {
        "losses": ["0x1.091df58927489p+1", "0x1.0befe45ddfa44p+0",
                   "0x1.7f8c3d6940724p-1"],
        "grad_norms": {"entity": "0x1.7ef76bda09527p-2",
                       "relation": "0x1.a8380121a9708p-3"},
        "param_norms": {"entity": "0x1.ac768ac9f1837p+5",
                        "relation": "0x1.c22fd15992ddfp+0"},
        "params_sha256": "db9f8676b54c59ca6668155a1070c7f6"
                         "a3d50e71be8d71ec0aaf1931853de605",
        "grads_sha256": "86a6959bd58aa86ae94e01c7b37a4945"
                        "c62526e17251df2872fb2f5515a5cb3b",
    },
    "bfloat16": {
        "losses": ["0x1.0a00000000000p+1", "0x1.0c00000000000p+0",
                   "0x1.8000000000000p-1"],
        "grad_norms": {"entity": "0x1.78a44e5031766p-2",
                       "relation": "0x1.9fe3b5ceac37cp-3"},
        "param_norms": {"entity": "0x1.ac740023bfdacp+5",
                        "relation": "0x1.c103f3ea4f484p+0"},
        "params_sha256": "4771d4e56b77aa9ba26f3534288ca92d"
                         "c131159e8e82db23ba035a283c75fdec",
        "grads_sha256": "468ebf27561bb30da8cb967bcc33cd6f"
                        "a91ec1cd2135895eb04c44f4712affe8",
    },
}


def _sha256(leaves):
    return hashlib.sha256(b"".join(np.ascontiguousarray(leaves[k]).tobytes()
                                   for k in sorted(leaves))).hexdigest()


def _norms(leaves):
    return {k: float(np.sqrt(np.sum(v.ravel() ** 2))).hex()
            for k, v in sorted(leaves.items())}


@pytest.mark.parametrize("precision", ["float64", "bfloat16"])
def test_transe_reference_reproduces_its_recorded_values(small_graph,
                                                         precision):
    triples, n_ent, n_rel = small_graph
    model = spec.train_reference("transe")
    tc = dict(spec.config("go40k-transe")["train"])
    rnd = {"float64": reference.float64, "bfloat16": reference.bfloat16}
    params0 = model.init(7, n_ent, n_rel, 200)
    r = model.reference(params0, triples, [11, 12, 13], n_ent, tc,
                        rnd=rnd[precision])
    got = {"losses": [float(x).hex() for x in r["losses"]],
           "grad_norms": _norms(r["grads"]),
           "param_norms": _norms(r["params"]),
           "params_sha256": _sha256(r["params"]),
           "grads_sha256": _sha256(r["grads"])}
    assert got == GOLDEN[precision]
