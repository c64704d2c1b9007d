"""Fixtures of the chip benchmark's CPU tests (see ``rehearsal.py``)."""
from __future__ import annotations

import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


@pytest.fixture()
def tiny_tree(tmp_path, monkeypatch):
    """A checkout-shaped tree of tiny cells, with ``spec`` pointed at it."""
    from benchmarks.chip import rehearsal, spec

    here = rehearsal.build_tree(tmp_path)
    monkeypatch.setattr(spec, "HERE", here)
    monkeypatch.setattr(spec, "ROOT", tmp_path)
    return tmp_path


@pytest.fixture()
def rehearse(tiny_tree):
    """``rehearse(cell, seed, seconds, trace)`` -> the result object."""
    from benchmarks.chip import rehearsal
    return rehearsal.rehearse
