"""The main path's device programs, compiled for a TPU v5e at real size.

Nothing runs: each test lowers and compiles one program for a described
(not attached) ``v5e:2x2`` topology, which raises whatever the chip's
compiler would refuse — a primitive the Pallas TPU lowering lacks, an
unaligned block, a sharding the partitioner cannot place.  Shapes are the
served ones: GO width (40,000 classes, d=200), k=10, the 8192-row
streaming slab, the trainer's batch of 1024 with 32 negatives.

The topology is described inside a module fixture — never on import —
and the tests skip where it cannot be; the persistent compilation cache
is off around the compiles (an entry written here cannot be read back
without a chip).  All of these stay in this one file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

D, K = 200, 10
GO_N = 40_000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache

    def cache(on):
        jax.config.update("jax_enable_compilation_cache", on)
        compilation_cache.reset_cache()

    cache(False)
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        cache(was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    cache(was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("with_norms", [False, True])
@pytest.mark.parametrize("n", [8192, GO_N])
@pytest.mark.parametrize("q", [1, 64])
def test_topk_pallas_kernel_compiles(one_chip, q, n, with_norms):
    from repro.kernels.topk_similarity import topk_cosine_pallas

    def call(qv, e, excl, nrm):
        return topk_cosine_pallas(qv, e, K, exclude_rows=excl, norms=nrm,
                                  block_n=1024, interpret=False)

    args = (_spec((q, D), jnp.float32, one_chip),
            _spec((n, D), jnp.float32, one_chip),
            _spec((q,), jnp.int32, one_chip),
            _spec((n,), jnp.float32, one_chip) if with_norms else None)
    compiled = jax.jit(call).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_stream_step_ref_compiles(one_chip):
    from repro.kernels.ops import STREAM_BLOCK_ROWS, _stream_step_ref

    q = 64
    step = functools.partial(_stream_step_ref, k=K, has_norms=True)
    compiled = jax.jit(step).lower(
        _spec((q, D), jnp.float32, one_chip),
        _spec((STREAM_BLOCK_ROWS, D), jnp.float32, one_chip),
        _spec((STREAM_BLOCK_ROWS,), jnp.float32, one_chip),
        _spec((), jnp.int32, one_chip), _spec((), jnp.int32, one_chip),
        _spec((q,), jnp.int32, one_chip),
        _spec((q, K), jnp.float32, one_chip),
        _spec((q, K), jnp.int32, one_chip)).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("q", [1, 2, 4, 8, 16, 32])
def test_resident_topk_compiles(one_chip, q):
    """The one-launch batch over a resident GO table: 40,000 rows padded
    to five slabs, at each scheduler bucket up to 32 queries."""
    from repro.kernels.ops import STREAM_BLOCK_ROWS, _resident_topk

    n_pad = -(-GO_N // STREAM_BLOCK_ROWS) * STREAM_BLOCK_ROWS
    compiled = _resident_topk.lower(
        _spec((n_pad, D), jnp.float32, one_chip),
        _spec((n_pad,), jnp.float32, one_chip),
        _spec((), jnp.int32, one_chip),
        _spec((q,), jnp.int32, one_chip),
        k=K, block_rows=STREAM_BLOCK_ROWS, exclude_self=True).compile()
    text = compiled.as_text()
    assert f"f32[{n_pad},{D}]" in text
    # no launch copies the table, nor a slab of it, to another layout or
    # memory space
    assert not re.search(
        rf"= f32\[({D},{n_pad}|{n_pad},{D}|{D},{STREAM_BLOCK_ROWS}|"
        rf"{STREAM_BLOCK_ROWS},{D})\]\S* (copy|copy-start|transpose)\(",
        text)


def test_transe_train_step_compiles(one_chip):
    from repro.kge import make_model
    from repro.kge.train import TrainConfig, make_train_step
    from repro.optim import OPTIMIZERS

    cfg = TrainConfig(batch_size=1024, num_negs=32)
    model = make_model("transe", GO_N, 3, dim=D)
    optimizer = OPTIMIZERS[cfg.optimizer](cfg.lr)
    step, _ = make_train_step(model, optimizer, cfg)
    key = jax.random.key(0)
    params = jax.eval_shape(model.init, key)
    opt_state = jax.eval_shape(optimizer.init, params)
    on_chip = functools.partial(jax.tree.map, lambda s: _spec(
        s.shape, s.dtype, one_chip))
    key_spec = jax.eval_shape(lambda: key)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(opt_state),
        _spec((cfg.batch_size, 3), jnp.int32, one_chip),
        _spec(key_spec.shape, key_spec.dtype, one_chip)).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("use_pallas", [False, True])
def test_sharded_topk_compiles_on_four_chips(topo, use_pallas):
    from repro.kernels.ops import _sharded_topk_fn

    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    rows = NamedSharding(mesh, P("data", None))
    rep = NamedSharding(mesh, P())
    q = 64
    run = _sharded_topk_fn(mesh, "data", GO_N, GO_N, K, use_pallas, False,
                           True)
    compiled = run.lower(
        _spec((q, D), jnp.float32, rep),
        _spec((GO_N, D), jnp.float32, rows),
        _spec((GO_N,), jnp.float32, NamedSharding(mesh, P("data"))),
        _spec((q,), jnp.int32, rep)).compile()
    text = compiled.as_text()
    assert "all-gather" in text
    assert ("tpu_custom_call" in text) == use_pallas
