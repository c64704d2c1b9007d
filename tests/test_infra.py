"""Infrastructure: roofline HLO parser, sharding specs, data pipeline,
optimizers, walks."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from benchmarks.roofline import (collective_summary, model_flops,
                                 parse_collectives, roofline_terms)


HLO_SAMPLE = """
HloModule jit_step
fused_computation {
  p0 = bf16[8,128]{1,0} parameter(0)
  ROOT add = bf16[8,128]{1,0} add(p0, p0)
}
ENTRY main {
  %p = bf16[8,128]{1,0} parameter(0)
  %ag = bf16[128,128]{1,0} all-gather(bf16[8,128]{1,0} %p), dimensions={0}
  %ar = f32[64]{0} all-reduce(f32[64]{0} %x), replica_groups={}
  %rs = bf16[8,64]{1,0} reduce-scatter(bf16[8,128]{1,0} %y), dimensions={1}
  %cp = u32[4]{0} collective-permute(u32[4]{0} %z)
  %a2a = (f32[2,8]{1,0}, f32[2,8]{1,0}) all-to-all(f32[2,8]{1,0} %a, f32[2,8]{1,0} %b)
  %ars = bf16[16]{0} all-reduce-start(bf16[16]{0} %w)
  %ard = bf16[16]{0} all-reduce-done(bf16[16]{0} %ars)
  ROOT %t = tuple()
}
"""


def test_parse_collectives_kinds_and_bytes():
    ops = parse_collectives(HLO_SAMPLE)
    kinds = sorted(o.kind for o in ops)
    assert kinds == ["all-gather", "all-reduce", "all-reduce",
                     "all-to-all", "collective-permute", "reduce-scatter"]
    ag = next(o for o in ops if o.kind == "all-gather")
    assert ag.result_bytes == 128 * 128 * 2
    assert ag.operand_bytes == 8 * 128 * 2
    assert ag.traffic == 128 * 128 * 2            # max(result, operand)
    rs = next(o for o in ops if o.kind == "reduce-scatter")
    assert rs.traffic == 8 * 128 * 2              # operand side
    a2a = next(o for o in ops if o.kind == "all-to-all")
    assert a2a.result_bytes == 2 * (2 * 8 * 4)


def test_parse_ignores_non_collectives_and_done_ops():
    ops = parse_collectives(HLO_SAMPLE)
    # all-reduce-start counted once, -done not double counted
    n_ar = sum(1 for o in ops if o.kind == "all-reduce")
    assert n_ar == 2


def test_collective_summary():
    s = collective_summary(HLO_SAMPLE)
    assert s["n_ops"] == 6
    assert s["traffic_bytes"] > 0
    assert set(s["by_kind"]) == {"all-gather", "all-reduce", "reduce-scatter",
                                 "all-to-all", "collective-permute"}


def test_roofline_terms_pick_dominant():
    t = roofline_terms(197e12, 10e9, 1e9)         # 1s compute, tiny rest
    assert t["dominant"] == "compute_s"
    assert abs(t["compute_s"] - 1.0) < 1e-9
    t = roofline_terms(1e9, 819e9, 1e9)           # 1s memory
    assert t["dominant"] == "memory_s"


def test_model_flops_semantics():
    n = 1_000_000
    assert model_flops(n, "train", 4, 128) == 6 * n * 4 * 128
    assert model_flops(n, "prefill", 4, 128) == 2 * n * 4 * 128
    assert model_flops(n, "decode", 4, 128) == 2 * n * 4
    assert model_flops(n, "train", 4, 4096, dec_len=448) == 6 * n * 4 * 448


# ------------------------- sharding specs ------------------------- #
def test_param_pspec_rules():
    from repro.models import get_config
    from repro.models.sharding import param_pspec
    cfg = get_config("qwen2_72b").with_(kv_groups=16)
    assert param_pspec(cfg, ("embed",), 2, 16) == P(None, "model")
    assert param_pspec(cfg, ("lm_head",), 2, 16) == P(None, "model")
    assert param_pspec(cfg, ("layers", "attn", "wq", "w"), 3, 16) == \
        P(None, None, "model")
    assert param_pspec(cfg, ("layers", "attn", "wo", "w"), 3, 16) == \
        P(None, "model", None)
    assert param_pspec(cfg, ("layers", "mlp", "down", "w"), 3, 16) == \
        P(None, "model", None)
    assert param_pspec(cfg, ("layers", "ln1", "scale"), 2, 16) == P(None, None)

    moe64 = get_config("olmoe_1b_7b").with_(kv_groups=16)
    assert param_pspec(moe64, ("layers", "moe", "gate"), 4, 16) == \
        P(None, "model", None, None)          # expert-parallel (64 % 16 == 0)
    moe8 = get_config("grok_1_314b").with_(kv_groups=16)
    assert param_pspec(moe8, ("layers", "moe", "gate"), 4, 16) == \
        P(None, None, None, "model")          # tensor-parallel inside expert
    assert param_pspec(moe8, ("layers", "moe", "down"), 4, 16) == \
        P(None, None, "model", None)

    ssm = get_config("falcon_mamba_7b")
    assert param_pspec(ssm, ("layers", "mamba", "in_proj", "w"), 3, 16) == \
        P(None, None, "model")
    assert param_pspec(ssm, ("layers", "mamba", "A_log"), 3, 16) == \
        P(None, "model", None)


def test_batch_pspec_replicates_indivisible_batch():
    import os
    from repro.models.sharding import batch_pspec
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    assert batch_pspec(mesh, 4, 2) == P(("data",), None)
    # batch=1 on 16-way data axis -> replicate (long_500k)
    # emulate via divisibility logic directly
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    assert batch_pspec(FakeMesh(), 1, 2) == P(None, None)
    assert batch_pspec(FakeMesh(), 256, 2) == P(("data",), None)


# ------------------------- data pipeline ------------------------- #
def test_triple_loader_epochs_cover_all():
    from repro.data.triples import TripleLoader
    trips = np.arange(30).reshape(10, 3)
    loader = TripleLoader(trips, batch_size=4, seed=0)
    it = iter(loader)
    seen = set()
    for _ in range(loader.steps_per_epoch):
        b = next(it)
        assert b.shape == (4, 3)
        seen.update(b[:, 0].tolist())
    assert len(seen) >= 8          # shuffled coverage (padding may repeat)


def test_walks_corpus(tiny_go):
    from repro.data import corpus, skipgram_pairs
    walks, vocab, pad = corpus(tiny_go, jax.random.key(0),
                               walks_per_entity=2, walk_length=3)
    w = np.asarray(walks)
    assert w.ndim == 2
    assert vocab >= tiny_go.num_entities
    pairs = skipgram_pairs(walks, window=2, pad_token=pad, seed=0)
    assert pairs.shape[1] == 2
    assert (pairs != pad).all()


@pytest.mark.slow
def test_adam_converges_quadratic():
    from repro.optim import adam
    opt = adam(0.1)
    params = {"x": jnp.asarray(5.0)}
    state = opt.init(params)
    for _ in range(200):
        g = {"x": 2 * params["x"]}
        params, state = opt.update(g, state, params)
    assert abs(float(params["x"])) < 1e-2


def test_snapshot_store_roundtrip(tmp_path):
    from repro.checkpoint import SnapshotStore
    store = SnapshotStore(tmp_path)
    arrays = {"embeddings": np.random.rand(5, 4).astype(np.float32),
              "entity_ids": np.asarray(["a", "b", "c", "d", "e"])}
    store.save("go", "v1", "transe", arrays, {"dim": 4})
    arrs, meta = store.load("go", "v1", "transe")
    np.testing.assert_array_equal(arrs["embeddings"], arrays["embeddings"])
    assert meta["dim"] == 4
    assert store.versions("go") == ["v1"]
    assert store.models("go", "v1") == ["transe"]


def test_lr_schedules():
    from repro.optim.schedules import constant, inverse_sqrt, linear_warmup_cosine
    import jax.numpy as jnp
    c = constant(0.1)
    assert float(c(0)) == float(c(1000)) == pytest.approx(0.1)
    s = linear_warmup_cosine(1.0, warmup_steps=10, total_steps=100)
    assert float(s(0)) == 0.0
    assert float(s(10)) == pytest.approx(1.0, rel=1e-5)
    assert float(s(100)) == pytest.approx(0.1, rel=1e-4)   # final_frac
    assert float(s(55)) < float(s(20))                     # decaying
    i = inverse_sqrt(1.0, warmup_steps=16)
    assert float(i(16)) == pytest.approx(1.0, rel=1e-5)
    assert float(i(64)) == pytest.approx(0.5, rel=1e-4)


def test_compile_cache_dir(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set in code;
    without it the cache sits at the fixed <repo>/.jax_cache."""
    from repro.launch import compile_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == \
            before["jax_compilation_cache_dir"]
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo_cache = Path(__file__).resolve().parents[1] / ".jax_cache"
        assert compile_cache.enable_compile_cache() == str(repo_cache)
        assert jax.config.jax_compilation_cache_dir == str(repo_cache)
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
