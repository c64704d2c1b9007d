"""Production mesh definitions.

Functions (not module-level constants) so importing this module never
touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the code here shards through
    ``with_sharding_constraint`` and jit shardings (GSPMD), which the
    default Explicit axes of ``jax.make_mesh`` refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e: 16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests on CPU)."""
    return _auto_mesh((data, model), ("data", "model"))


def make_serving_mesh(min_devices: int = 2):
    """1-D ("data",) mesh over all local devices for the sharded serving
    top-k path (embedding tables laid out P("data", None); see
    kernels.ops.topk_cosine_sharded). Returns None when fewer than
    ``min_devices`` are available — the caller then uses the unchanged
    single-device path."""
    n = jax.device_count()
    if n < min_devices:
        return None
    return _auto_mesh((n,), ("data",))
