"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The script itself runs only on a TPU (and must refuse anything else); its
phases are plain functions, so the test drives them here with small
shapes, Pallas in interpret mode: release -> HTTP serve -> oracle ->
Pallas on one device, and the sharded top-k on four virtual devices.
"""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    return env


def test_single_chip_phases_on_cpu(tmp_path):
    cs = _chip_smoke()
    rep = cs.run_single(str(tmp_path), n_terms=300, dim=16, steps=3,
                        burst=16, join_classes=40)
    assert rep["batches"] < 16                  # the burst was batched
    assert rep["join"]["queries"] == 40
    for phase in ("burst", "join", "pallas"):
        r = rep[phase]
        assert r["total"] == r["queries"] * cs.K
        assert r["max_err"] <= cs.SCORE_ATOL


def test_sharded_phase_on_four_cpu_devices():
    code = textwrap.dedent(f"""
        import os, sys, json
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, {str(REPO)!r})
        import chip_smoke
        rep = chip_smoke.run_sharded(4, n_terms=1000, dim=200, queries=8)
        print(json.dumps({{k: int(v["total"]) for k, v in rep.items()}}))
    """)
    out = subprocess.run([sys.executable, "-c", code], env=_env(),
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    assert rep == {"sharded": 80, "single": 80}


def test_script_refuses_without_tpu():
    out = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         env=_env(), capture_output=True, text=True,
                         timeout=300, cwd=REPO)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout
