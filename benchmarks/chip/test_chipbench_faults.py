"""A whole run of each cell of BENCHMARK.json, on the CPU at a tiny size
and under the cell's own name, with the chip's look skipped, must come
out correct; with the timed path or the model's reference broken
underneath, it must come out not correct, once for each fault its cell
can have."""
from __future__ import annotations

import pytest

from benchmarks.chip.rehearsal import cells

TRAIN_CELLS = cells(1, "kge_train")


@pytest.mark.parametrize("cell", cells(1))
def test_sound_run_is_correct(rehearse, cell):
    r = rehearse(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"


def _alter(fault):
    """Wrap EmbeddingIndex.top_k_rows so its answers come out wrong."""
    from repro.core import serving

    orig = serving.EmbeddingIndex.top_k_rows

    def top_k_rows(self, rows, k=10, exclude_self=True):
        out = orig(self, rows, k, exclude_self)
        if fault == "score":
            for hits in out:
                hits[0].score += 1e-3
        elif fault == "neighbour":
            for hits, row in zip(out, rows):
                far = (int(row) + len(self.entity_ids) // 2) % len(
                    self.entity_ids)
                hits[-1].identifier = self.entity_ids[far]
                hits[-1].label = self.labels[far]
        elif fault == "half_batch":
            half = max(1, len(out) // 2)
            out = out[:half] + out[:half][:len(out) - half]
        return out

    return serving.EmbeddingIndex, "top_k_rows", top_k_rows


@pytest.mark.parametrize("fault", ["score", "neighbour", "half_batch"])
def test_altered_answers_fail(rehearse, monkeypatch, fault):
    monkeypatch.setattr(*_alter(fault))
    r = rehearse("go40k-topk-uniform")
    assert not r["correct"], r["checks"]


def _broken_step(fault):
    from repro.kge import train

    orig = train.make_train_step

    def make_train_step(model, optimizer, cfg):
        step, loss_of = orig(model, optimizer, cfg)

        def broken(params, opt_state, triples, key):
            if fault == "unchanged":
                _, _, loss = step(params, opt_state, triples, key)
                return params, opt_state, loss
            return step(params, opt_state, triples[:triples.shape[0] // 2],
                        key)

        return broken, loss_of

    return train, "make_train_step", make_train_step


@pytest.mark.parametrize("cell", TRAIN_CELLS)
@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_train_step_fails(rehearse, monkeypatch, fault, cell):
    monkeypatch.setattr(*_broken_step(fault))
    r = rehearse(cell)
    assert not r["correct"], r["checks"]
    if fault == "unchanged":
        assert r["checks"]["update_gap"]["value"] == pytest.approx(1.0)


def _config(cell):
    """The tiny tree's configuration file of ``cell``, and its contents."""
    import json

    from benchmarks.chip import spec

    cfg_name = spec.workload(cell)["config"]
    path = spec.HERE / "configs" / f"{cfg_name}.json"
    return path, json.loads(path.read_text())


#: appended to a copy of a model's reference file: every loss it returns
#: is scaled by 1.01
SCALED_LOSS = """

_unscaled = reference


def reference(*args, **kwargs):
    out = _unscaled(*args, **kwargs)
    return {**out, "losses": [x * 1.01 for x in out["losses"]]}
"""


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_altered_reference_fails(tiny_tree, rehearse, cell):
    """The run checks against the reference that its configuration's
    ``model`` names: the same file with its loss scaled by 1.01 fails."""
    from benchmarks.chip import spec

    _, cfg = _config(cell)
    ref = spec.HERE / "references" / f"{cfg['model']}.py"
    ref.write_text(ref.read_text() + SCALED_LOSS)
    r = rehearse(cell)
    assert not r["correct"], r["checks"]
    assert r["checks"]["loss_gap"]["value"] > 0.009


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_missing_reference_stops_before_a_device(tiny_tree, rehearse,
                                                 monkeypatch, cell):
    import json

    from benchmarks.chip import run, spec

    path, cfg = _config(cell)
    path.write_text(json.dumps({**cfg, "model": "no-reference-model"}))

    def open_chips(*_, **__):
        raise AssertionError("a device was opened")

    monkeypatch.setattr(run, "open_chips", open_chips)
    with pytest.raises(spec.SpecError,
                       match="references/no-reference-model.py"):
        rehearse(cell)


@pytest.mark.parametrize("p_norm,margin", [(2, 0.5), (1, 1.0)])
def test_config_train_group_reaches_the_model(p_norm, margin):
    """The trainer runs the configuration's loss, margin and norm, not the
    model's defaults."""
    from benchmarks.chip import spec, train_cell

    cfg = spec.config(spec.workload(TRAIN_CELLS[0])["config"])
    cfg = {**cfg, "n_terms": 300, "dim": 16,
           "train": {**cfg["train"], "p_norm": p_norm, "margin": margin}}
    tr = train_cell.Training(cfg, {"chunk_steps": 3, "check_steps": 3}, 5)
    got = tr.trainer.model.spec
    tr.free()
    assert (got.loss, got.margin, got.p_norm) == (
        cfg["train"]["loss"], margin, p_norm)


def test_no_chip_exits_nonzero_and_prints_no_result(tmp_path):
    import os
    import subprocess
    import sys

    from benchmarks.chip import spec

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for cell in ("go40k-train-transe", "go40k-topk-zipf"):
        p = subprocess.run(
            [sys.executable, str(spec.HERE / "run.py"), "--workload", cell,
             "--seed", "1", "--seconds", "1"],
            cwd=tmp_path, env=env, capture_output=True, text=True,
            timeout=120)
        assert p.returncode != 0
        assert p.stdout.strip() == ""
        assert "no TPU" in p.stderr


def test_only_the_benchmark_files_are_not_enough(tmp_path):
    """A checkout holding only BENCHMARK.json and benchmarks/chip has no
    system to test: the run fails and prints no result."""
    import os
    import shutil
    import subprocess
    import sys

    from benchmarks.chip import spec

    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "go40k-train-transe", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, env={**env, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


def _sharded(tmp_path, cell, fault=None):
    import json
    import os
    import subprocess
    import sys

    from benchmarks.chip import spec

    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    p = subprocess.run(
        [sys.executable, str(spec.HERE / "rehearsal.py"), str(tmp_path),
         cell] + ([fault] if fault else []),
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", cells(4))
def test_sharded_cell_on_four_devices(tmp_path, cell):
    r = _sharded(tmp_path, cell)
    assert r["correct"], r["checks"]
    assert r["device"]["count"] == 4


@pytest.mark.parametrize("cell", cells(4))
def test_sharded_cell_without_the_exchange_fails(tmp_path, cell):
    r = _sharded(tmp_path, cell, "drop_exchange")
    assert not r["correct"], r["checks"]
