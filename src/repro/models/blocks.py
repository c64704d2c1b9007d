"""Layer blocks for the model zoo.

Every block is a pair of pure functions ``*_init(key, cfg) -> params`` and
``*_apply(params, x, cfg, ...) -> y`` operating on the residual stream
(B, S, d). Decode variants thread an explicit cache.

Blocks:
  * attention block  — GQA in the grouped-MHA view (config.padded_heads /
    kv repeated to cfg.groups), full/sliding-window, RoPE.
  * MoE block        — top-k router, capacity-bounded scatter dispatch into
    an (E, C, d) buffer, grouped expert GEMMs, weighted combine. This is
    the GShard/MaxText dropping formulation, scatter-based so no
    (T, E, C) one-hot ever materializes.
  * Mamba block      — mamba1 selective scan (chunked associative scan).
  * RG-LRU block     — RecurrentGemma recurrent block (gated linear
    recurrence + short conv), chunked scan.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import chunked_attention, decode_attention, swa_attention
from .config import ArchConfig
from .layers import apply_norm, dense, dense_init, mlp, mlp_init, norm_init, rope_qk

Params = Dict[str, Any]


# ===================================================================== #
# Attention block
# ===================================================================== #
def attn_init(key, cfg: ArchConfig, *, window: Optional[int] = None) -> Params:
    d, hd, kv = cfg.d_model, cfg.hd, cfg.n_kv_heads
    hp = cfg.padded_heads()
    ks = jax.random.split(key, 4)
    dt = cfg.jdtype
    p = {
        "wq": dense_init(ks[0], d, hp * hd, dt, bias=cfg.qkv_bias),
        "wk": dense_init(ks[1], d, kv * hd, dt, bias=cfg.qkv_bias),
        "wv": dense_init(ks[2], d, kv * hd, dt, bias=cfg.qkv_bias),
        "wo": dense_init(ks[3], hp * hd, d, dt),
    }
    # zero the padding q-heads: their q columns and out-proj rows. Forward
    # is then exactly the published n_heads model (tests/test_models_padding).
    if hp != cfg.n_heads:
        mask = _pad_head_mask(cfg)                     # (hp,) 1=real 0=pad
        colmask = jnp.repeat(mask, hd)[None, :].astype(dt)
        p["wq"]["w"] = p["wq"]["w"] * colmask
        if "b" in p["wq"]:
            p["wq"]["b"] = p["wq"]["b"] * colmask[0]
        p["wo"]["w"] = p["wo"]["w"] * colmask.T
    return p


def _pad_head_mask(cfg: ArchConfig) -> jnp.ndarray:
    """(hp,) mask — q-heads are laid out in n_kv_heads groups of g' slots,
    the first g real heads of each group are live."""
    g = cfg.n_heads // cfg.n_kv_heads
    gp = cfg.padded_heads() // cfg.n_kv_heads
    m = jnp.zeros((cfg.n_kv_heads, gp))
    m = m.at[:, :g].set(1.0)
    return m.reshape(-1)


def _project_qkv(p: Params, x: jnp.ndarray, cfg: ArchConfig):
    """x (B,S,d) -> q (B,S,G,H,hd), k/v (B,S,G,hd) with KV repeated to G."""
    B, S, _ = x.shape
    hd, G = cfg.hd, cfg.groups
    hp, kv = cfg.padded_heads(), cfg.n_kv_heads
    q = dense(p["wq"], x).reshape(B, S, G, hp // G, hd)
    k = dense(p["wk"], x).reshape(B, S, kv, hd)
    v = dense(p["wv"], x).reshape(B, S, kv, hd)
    if G != kv:
        r = G // kv
        k = jnp.repeat(k, r, axis=2)
        v = jnp.repeat(v, r, axis=2)
    return q, k, v


def attn_apply(p: Params, x: jnp.ndarray, cfg: ArchConfig, *,
               causal: bool = True, window: Optional[int] = None,
               q_offset: int = 0) -> jnp.ndarray:
    """Train/prefill attention over full sequence x (B,S,d)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg)
    if cfg.rope_theta > 0:
        pos = q_offset + jnp.arange(S)
        q, k = rope_qk(q, k, pos, pos, cfg.rope_theta)
    if window is not None:
        o = swa_attention(q, k, v, window=window, q_offset=q_offset)
    elif causal:
        o = chunked_attention(q, k, v, causal=True, q_offset=q_offset)
    else:
        o = chunked_attention(q, k, v, causal=False)
    o = o.reshape(B, S, -1)
    return dense(p["wo"], o)


def attn_prefill(p: Params, x: jnp.ndarray, cfg: ArchConfig, *,
                 window: Optional[int] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Like attn_apply but also returns the (post-RoPE) KV for the cache.

    Returns (out (B,S,d), k_cache (B,G,Sc,hd), v_cache (B,G,Sc,hd)) where
    Sc = window for SWA (rolling layout: slot = pos % window) else S.
    """
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg)
    if cfg.rope_theta > 0:
        pos = jnp.arange(S)
        q, k = rope_qk(q, k, pos, pos, cfg.rope_theta)
    if window is not None:
        o = swa_attention(q, k, v, window=window)
        W = window
        if S >= W:
            # last W positions, laid out rolling: slot i holds pos p with
            # p % W == i. Positions S-W..S-1 -> roll so slot (p % W).
            kt, vt = k[:, S - W:], v[:, S - W:]
            shift = (S - W) % W
            kc = jnp.roll(kt, shift, axis=1)
            vc = jnp.roll(vt, shift, axis=1)
        else:
            pad = W - S
            kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        o = chunked_attention(q, k, v, causal=True)
        kc, vc = k, v
    o = dense(p["wo"], o.reshape(B, S, -1))
    return o, jnp.moveaxis(kc, 1, 2), jnp.moveaxis(vc, 1, 2)


def attn_decode(p: Params, x: jnp.ndarray, k_cache: jnp.ndarray,
                v_cache: jnp.ndarray, pos: jnp.ndarray, cfg: ArchConfig, *,
                window: Optional[int] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Single-token decode. x (B,1,d); caches (B,G,Sc,hd); pos scalar.

    Writes the new KV at slot (pos % window) for SWA, pos otherwise, then
    attends over valid slots. Returns (out (B,1,d), k_cache, v_cache).
    """
    B = x.shape[0]
    q, k, v = _project_qkv(p, x, cfg)                 # q (B,1,G,H,hd)
    if cfg.rope_theta > 0:
        ppos = jnp.full((1,), 0, jnp.int32) + pos
        q, k = rope_qk(q, k, ppos, ppos, cfg.rope_theta)
    Sc = k_cache.shape[2]
    slot = pos % Sc if window is not None else pos
    kn = jnp.moveaxis(k, 1, 2)                        # (B,G,1,hd)
    vn = jnp.moveaxis(v, 1, 2)
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, kn.astype(k_cache.dtype), slot, axis=2)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, vn.astype(v_cache.dtype), slot, axis=2)
    n_valid = jnp.minimum(pos + 1, Sc)
    o = decode_attention(q, k_cache, v_cache, n_valid)
    o = dense(p["wo"], o.reshape(B, 1, -1))
    return o, k_cache, v_cache


def quantize_kv(t: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-(…, slot) int8 quantization over the trailing hd axis.
    t (..., hd) -> (int8 (..., hd), scale f32 (...,))."""
    amax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def attn_decode_inplace(p: Params, x: jnp.ndarray, k_cache: jnp.ndarray,
                        v_cache: jnp.ndarray, layer: jnp.ndarray,
                        pos: jnp.ndarray, cfg: ArchConfig, *,
                        window: Optional[int] = None,
                        k_scale: Optional[jnp.ndarray] = None,
                        v_scale: Optional[jnp.ndarray] = None):
    """Like attn_decode but writes the new slot directly into the STACKED
    (L, B, G, S, hd) caches at (layer, :, :, slot) — one (B, G, 1, hd)
    write instead of re-emitting the layer's whole cache.

    int8 KV mode (§Perf qwen2 decode Q3): when k_scale/v_scale
    (L, B, G, S) are given, the caches are int8; the new slot is quantized
    on write and rows are dequantized for the attention dot — halving the
    dominant HBM term of 32k decode."""
    B = x.shape[0]
    q, k, v = _project_qkv(p, x, cfg)                 # q (B,1,G,H,hd)
    if cfg.rope_theta > 0:
        ppos = jnp.full((1,), 0, jnp.int32) + pos
        q, k = rope_qk(q, k, ppos, ppos, cfg.rope_theta)
    Sc = k_cache.shape[3]
    slot = pos % Sc if window is not None else pos
    kn = jnp.moveaxis(k, 1, 2)[None]                  # (1,B,G,1,hd)
    vn = jnp.moveaxis(v, 1, 2)[None]
    zero = jnp.zeros((), jnp.int32)
    idx = (layer, zero, zero, slot, zero)
    quant = k_scale is not None
    if quant:
        kn, ks_new = quantize_kv(kn)
        vn, vs_new = quantize_kv(vn)
        k_scale = jax.lax.dynamic_update_slice(k_scale, ks_new, idx[:-1])
        v_scale = jax.lax.dynamic_update_slice(v_scale, vs_new, idx[:-1])
    k_cache = jax.lax.dynamic_update_slice(k_cache, kn.astype(k_cache.dtype), idx)
    v_cache = jax.lax.dynamic_update_slice(v_cache, vn.astype(v_cache.dtype), idx)
    row_k = jax.lax.dynamic_index_in_dim(k_cache, layer, 0, keepdims=False)
    row_v = jax.lax.dynamic_index_in_dim(v_cache, layer, 0, keepdims=False)
    if quant:
        rks = jax.lax.dynamic_index_in_dim(k_scale, layer, 0, keepdims=False)
        rvs = jax.lax.dynamic_index_in_dim(v_scale, layer, 0, keepdims=False)
        row_k = dequantize_kv(row_k, rks, x.dtype)
        row_v = dequantize_kv(row_v, rvs, x.dtype)
    n_valid = jnp.minimum(pos + 1, Sc)
    o = decode_attention(q, row_k, row_v, n_valid)
    o = dense(p["wo"], o.reshape(B, 1, -1))
    if quant:
        return o, k_cache, v_cache, k_scale, v_scale
    return o, k_cache, v_cache


# ===================================================================== #
# Transformer block (attention + MLP), dense-family
# ===================================================================== #
def block_init(key, cfg: ArchConfig) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": norm_init(cfg.d_model, cfg.jdtype, cfg.norm),
        "attn": attn_init(k1, cfg),
        "ln2": norm_init(cfg.d_model, cfg.jdtype, cfg.norm),
        "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.jdtype, cfg.act),
    }


def block_apply(p: Params, x: jnp.ndarray, cfg: ArchConfig, *,
                causal: bool = True, window: Optional[int] = None) -> jnp.ndarray:
    x = x + attn_apply(p["attn"], apply_norm(p["ln1"], x), cfg,
                       causal=causal, window=window)
    x = x + mlp(p["mlp"], apply_norm(p["ln2"], x), cfg.act)
    return x


def block_prefill(p: Params, x: jnp.ndarray, cfg: ArchConfig, *,
                  window: Optional[int] = None):
    a, kc, vc = attn_prefill(p["attn"], apply_norm(p["ln1"], x), cfg,
                             window=window)
    x = x + a
    x = x + mlp(p["mlp"], apply_norm(p["ln2"], x), cfg.act)
    return x, kc, vc


def block_decode(p: Params, x: jnp.ndarray, kc, vc, pos, cfg: ArchConfig, *,
                 window: Optional[int] = None):
    a, kc, vc = attn_decode(p["attn"], apply_norm(p["ln1"], x), kc, vc, pos,
                            cfg, window=window)
    x = x + a
    x = x + mlp(p["mlp"], apply_norm(p["ln2"], x), cfg.act)
    return x, kc, vc


# ===================================================================== #
# MoE block
# ===================================================================== #
def _moe_dims(cfg: ArchConfig):
    """(E_virtual, ff_virtual, split). moe_ff_split=r slices each expert's
    ff into r column blocks => E*r virtual experts of ff/r each. down-proj
    halves sum, so dispatching a token to all r virtual slices of its
    routed expert computes exactly the original expert."""
    E, ff = cfg.moe.n_experts, cfg.d_ff
    r = max(1, cfg.moe_ff_split or 1)
    return E * r, ff // r, r


def moe_init(key, cfg: ArchConfig) -> Params:
    d, E = cfg.d_model, cfg.moe.n_experts
    Ev, ffv, _ = _moe_dims(cfg)
    dt = cfg.jdtype
    ks = jax.random.split(key, 4)
    sc = 1.0 / math.sqrt(d)
    scf = 1.0 / math.sqrt(cfg.d_ff)
    return {
        "router": (jax.random.normal(ks[0], (d, E), jnp.float32) * sc).astype(dt),
        "gate": (jax.random.normal(ks[1], (Ev, d, ffv), jnp.float32) * sc).astype(dt),
        "up": (jax.random.normal(ks[2], (Ev, d, ffv), jnp.float32) * sc).astype(dt),
        "down": (jax.random.normal(ks[3], (Ev, ffv, d), jnp.float32) * scf).astype(dt),
    }


def moe_apply(p: Params, x: jnp.ndarray, cfg: ArchConfig
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y (B,S,d), aux_loss scalar). Capacity-dropped top-k MoE.

    Dispatch is BLOCK-LOCAL (cfg.moe_dp_blocks blocks, = the data-axis size
    in production): each block routes its own tokens into its own
    (E, C_block, d) buffer slice, with per-block capacity. This is the
    standard expert-parallel design — it keeps the scatter, the expert
    GEMMs and the combine local to each data shard (the cross-device hop
    is only the expert-axis resharding), instead of every data shard
    replicating a GLOBAL-capacity buffer (which is catastrophically
    collective-bound — see EXPERIMENTS.md §Perf grok iteration 1).
    """
    B, S, d = x.shape
    E, K = cfg.moe.n_experts, cfg.moe.top_k
    Ev, ffv, r = _moe_dims(cfg)
    T = B * S
    nb = max(1, getattr(cfg, "moe_dp_blocks", 1) or 1)
    if T % nb:
        nb = 1
    Tb = T // nb
    xb = x.reshape(nb, Tb, d)

    logits = (xb @ p["router"]).astype(jnp.float32)           # (nb, Tb, E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, K)                          # (nb, Tb, K)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)

    if r > 1:
        # dispatch to every ff-slice of the routed expert (slices sum)
        idx = (idx[..., None] * r + jnp.arange(r)).reshape(nb, Tb, K * r)
        w = jnp.repeat(w, r, axis=-1)
        E, K = Ev, K * r
    C = max(1, int(math.ceil(Tb * K / E * cfg.moe.capacity_factor)))

    # rank of each (token, slot) within its expert queue, per block
    flat_idx = idx.reshape(nb, Tb * K)
    oh = jax.nn.one_hot(flat_idx, E, dtype=jnp.int32)         # (nb, Tb*K, E)
    rank = jnp.cumsum(oh, axis=1) - 1
    rank = jnp.take_along_axis(rank, flat_idx[..., None], axis=2)[..., 0]
    keep = rank < C
    slot = jnp.where(keep, flat_idx * C + rank, E * C)        # drop -> scratch

    src = jnp.repeat(xb, K, axis=1)                           # (nb, Tb*K, d)

    def scatter_block(slot_b, src_b):
        return jnp.zeros((E * C + 1, d), x.dtype).at[slot_b].add(src_b)

    buf = jax.vmap(scatter_block)(slot, src)                  # (nb, E*C+1, d)
    h = buf[:, :E * C].reshape(nb, E, C, d)
    h = _moe_constraint(h, cfg)

    pet = x.dtype
    g = jax.nn.silu(jnp.einsum("becd,edf->becf", h, p["gate"],
                               preferred_element_type=pet))
    u = jnp.einsum("becd,edf->becf", h, p["up"], preferred_element_type=pet)
    o = jnp.einsum("becf,efd->becd", g * u, p["down"],
                   preferred_element_type=pet)
    o = _moe_constraint(o, cfg)

    out_rows = jnp.concatenate(
        [o.reshape(nb, E * C, d), jnp.zeros((nb, 1, d), x.dtype)], axis=1)
    y = jnp.take_along_axis(out_rows, slot[..., None], axis=1)  # combine
    y = y * (w.reshape(nb, Tb * K, 1) * keep[..., None]).astype(x.dtype)
    y = y.reshape(nb, Tb, K, d).sum(axis=2)

    # load-balance aux loss (Switch): E * sum_e f_e * p_e, computed PER
    # BLOCK and averaged — the distributed semantics (each data shard sees
    # only its own tokens), kept identical between the gspmd and shard_map
    # implementations (tests/test_shard_map_moe.py). Over the ORIGINAL
    # experts; virtual ff-slices are a layout detail.
    E0 = cfg.moe.n_experts
    top1 = idx[..., 0] // r if r > 1 else idx[..., 0]       # (nb, Tb)
    f = jnp.mean(jax.nn.one_hot(top1, E0, dtype=jnp.float32), axis=1)
    pmean = jnp.mean(probs, axis=1)                          # (nb, E0)
    aux = E0 * jnp.mean(jnp.sum(f * pmean, axis=-1))
    return y.reshape(B, S, d), aux


def moe_apply_shard_map(p: Params, x: jnp.ndarray, cfg: ArchConfig, mesh
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Explicit expert-parallel MoE (§Perf MoE iteration 4).

    GSPMD's handling of the dispatch scatter / combine gather all-gathers
    the (T*K, d) dispatch arrays to every model shard (measured: ~40% of
    grok train traffic even after block-local capacity). shard_map makes
    the textbook pattern explicit instead:

      * tokens are data-sharded and REPLICATED across the model axis, so
        each device dispatch-scatters its local tokens into buffers for
        the experts RESIDENT on its model shard — zero collectives;
      * local expert FFN;
      * combine gathers locally (token-slots of non-resident experts hit
        the scratch row = 0) and one token-shaped psum over "model" sums
        the expert contributions — the only collective, (T_local, d).

    Per-data-shard capacity semantics are identical to moe_apply with
    moe_dp_blocks = |data axes| (tests assert equivalence on a CPU mesh).
    """
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    E0, K0 = cfg.moe.n_experts, cfg.moe.top_k
    Ev, ffv, r = _moe_dims(cfg)
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    n_model = mesh.shape["model"]
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    assert Ev % n_model == 0, (Ev, n_model)
    E_local = Ev // n_model

    def local_fn(xb, router, gate, up, down):
        # xb (B_loc, S, d); gate/up (E_local, d, ffv); down (E_local, ffv, d)
        Bl = xb.shape[0]
        T = Bl * S
        xf = xb.reshape(T, d)
        # retype tokens as model-varying: every shard's routing math is
        # bitwise identical, but this moves the (required) backward psum of
        # the dispatch to the TOKEN-shaped boundary dL/dxf instead of the
        # top_k-times-larger slot-shaped one (§Perf grok iteration 5).
        xf = jax.lax.pcast(xf, "model", to="varying")
        logits = (xf @ router).astype(jnp.float32)          # (T, E0)
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, K0)                   # (T, K0)
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
        if r > 1:
            idx = (idx[..., None] * r + jnp.arange(r)).reshape(T, K0 * r)
            w = jnp.repeat(w, r, axis=-1)
        K = K0 * r
        C = max(1, int(math.ceil(T * K / Ev * cfg.moe.capacity_factor)))

        flat_idx = idx.reshape(T * K)
        oh = jax.nn.one_hot(flat_idx, Ev, dtype=jnp.int32)
        rank = jnp.cumsum(oh, axis=0) - 1
        rank = jnp.take_along_axis(rank, flat_idx[:, None], axis=1)[:, 0]
        keep = rank < C

        m = jax.lax.axis_index("model")
        local_e = flat_idx - m * E_local                     # expert id on me
        mine = (local_e >= 0) & (local_e < E_local) & keep
        lslot = jnp.where(mine, local_e * C + rank, E_local * C)

        src = jnp.repeat(xf, K, axis=0)
        buf = jnp.zeros((E_local * C + 1, d), x.dtype).at[lslot].add(src)
        h = buf[:E_local * C].reshape(E_local, C, d)

        pet = x.dtype
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, gate,
                                   preferred_element_type=pet))
        u = jnp.einsum("ecd,edf->ecf", h, up, preferred_element_type=pet)
        o = jnp.einsum("ecf,efd->ecd", g * u, down,
                       preferred_element_type=pet)

        out_rows = jnp.concatenate(
            [o.reshape(E_local * C, d), jnp.zeros((1, d), x.dtype)], axis=0)
        y = out_rows[lslot]                                  # 0 if not mine
        y = y * (w.reshape(T * K, 1) * mine[:, None]).astype(x.dtype)
        y = y.reshape(T, K, d).sum(axis=1)
        # the ONE collective. Its cotangent is model-invariant (everything
        # downstream is replicated across "model"), so the transpose is the
        # identity — the default transpose would re-all-reduce a slot-shaped
        # f32 cotangent every layer (§Perf grok iteration 5).
        y = _psum_identity_bwd(y, "model")

        top1 = idx[:, 0] // r if r > 1 else idx[:, 0]
        f = jnp.mean(jax.nn.one_hot(top1, E0, dtype=jnp.float32), axis=0)
        pmean = jnp.mean(probs, axis=0)
        aux = E0 * jnp.sum(f * pmean)
        aux = jax.lax.pmean(aux, ("model",) + dp)   # invariant-ize copies
        return y.reshape(Bl, S, d), aux

    bspec = P(dp, None, None) if (dp and B % n_dp == 0 and B >= n_dp) \
        else P(None, None, None)
    y, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(bspec, P(), P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(bspec, P()),
    )(x, p["router"], p["gate"], p["up"], p["down"])
    return y, aux


def _psum_identity_bwd(y: jnp.ndarray, axis: str) -> jnp.ndarray:
    """psum whose backward is the identity. Valid whenever the consumer of
    the summed value computes identically on every shard of ``axis`` (the
    cotangent is then axis-invariant and the default psum-transpose is a
    redundant all-reduce)."""
    @jax.custom_vjp
    def f(v):
        return jax.lax.psum(v, axis)

    f.defvjp(lambda v: (jax.lax.psum(v, axis), None),
             # pcast: retype the (invariant) cotangent as axis-varying —
             # no data movement, just the manual-axes bookkeeping.
             lambda _, ct: (jax.lax.pcast(ct, axis, to="varying"),))
    return f(y)


def moe_dispatch(p: Params, x: jnp.ndarray, cfg: ArchConfig
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Route to the explicit-EP shard_map implementation when a mesh is
    active and the config requests it; pure-GSPMD path otherwise."""
    from . import runtime
    mesh = runtime.get_mesh()
    if mesh is not None and getattr(cfg, "moe_impl", "gspmd") == "shard_map":
        return moe_apply_shard_map(p, x, cfg, mesh)
    return moe_apply(p, x, cfg)


def _moe_constraint(t: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    """Pin the dispatch buffer (nb, E, C, d) to (data, expert-or-ff) axes.
    Only active in production lowering (moe_dp_blocks > 1 implies a mesh)."""
    if (getattr(cfg, "moe_dp_blocks", 1) or 1) <= 1:
        return t
    from jax.sharding import PartitionSpec as P
    dp = ("pod", "data") if (cfg.moe_dp_blocks or 1) > 16 else ("data",)
    Ev, _, _ = _moe_dims(cfg)
    if Ev % 16 == 0:                      # expert-parallel (olmoe, split grok)
        spec = P(dp, "model", None, None)
    else:                                  # ff tensor-parallel (grok)
        spec = P(dp, None, None, None)
    return jax.lax.with_sharding_constraint(t, spec)


def moe_block_init(key, cfg: ArchConfig) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": norm_init(cfg.d_model, cfg.jdtype, cfg.norm),
        "attn": attn_init(k1, cfg),
        "ln2": norm_init(cfg.d_model, cfg.jdtype, cfg.norm),
        "moe": moe_init(k2, cfg),
    }


def moe_block_apply(p: Params, x: jnp.ndarray, cfg: ArchConfig):
    x = x + attn_apply(p["attn"], apply_norm(p["ln1"], x), cfg, causal=True)
    y, aux = moe_dispatch(p["moe"], apply_norm(p["ln2"], x), cfg)
    return x + y, aux


def moe_block_prefill(p: Params, x: jnp.ndarray, cfg: ArchConfig):
    a, kc, vc = attn_prefill(p["attn"], apply_norm(p["ln1"], x), cfg)
    x = x + a
    y, _ = moe_dispatch(p["moe"], apply_norm(p["ln2"], x), cfg)
    return x + y, kc, vc


def moe_block_decode(p: Params, x: jnp.ndarray, kc, vc, pos, cfg: ArchConfig):
    a, kc, vc = attn_decode(p["attn"], apply_norm(p["ln1"], x), kc, vc, pos, cfg)
    x = x + a
    y, _ = moe_dispatch(p["moe"], apply_norm(p["ln2"], x), cfg)
    return x + y, kc, vc


# ===================================================================== #
# Mamba (mamba1 selective-scan) block
# ===================================================================== #
def _mamba_dims(cfg: ArchConfig):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    dtr = s.dt_rank or -(-cfg.d_model // 16)
    return d_in, dtr, s.d_state, s.d_conv


def mamba_init(key, cfg: ArchConfig) -> Params:
    d = cfg.d_model
    d_in, dtr, st, cw = _mamba_dims(cfg)
    dt = cfg.jdtype
    ks = jax.random.split(key, 6)
    A = jnp.tile(jnp.arange(1, st + 1, dtype=jnp.float32)[None, :], (d_in, 1))
    return {
        "in_proj": dense_init(ks[0], d, 2 * d_in, dt),
        "conv_w": (jax.random.normal(ks[1], (cw, d_in), jnp.float32)
                   / math.sqrt(cw)).astype(dt),
        "conv_b": jnp.zeros((d_in,), dt),
        "x_proj": dense_init(ks[2], d_in, dtr + 2 * st, dt),
        "dt_proj": dense_init(ks[3], dtr, d_in, dt, bias=True),
        "A_log": jnp.log(A),                               # (d_in, st) f32
        "D": jnp.ones((d_in,), jnp.float32),
        "out_proj": dense_init(ks[4], d_in, d, dt),
    }


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                 state: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Depthwise causal conv. x (B,S,d_in), w (cw,d_in).
    state (B,cw-1,d_in) holds the trailing inputs of the previous segment."""
    cw = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (cw - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(cw))
    return y + b


def _selective_scan_chunk(h0, dt, Bm, Cm, A, xc):
    """One chunk of the mamba scan.
    h0 (B,d_in,st) f32; dt (B,c,d_in); Bm/Cm (B,c,st); xc (B,c,d_in).
    Returns (h_last, y (B,c,d_in))."""
    dtf = dt.astype(jnp.float32)
    Abar = jnp.exp(dtf[..., None] * A)                        # (B,c,d_in,st)
    Bx = (dtf * xc.astype(jnp.float32))[..., None] * Bm.astype(jnp.float32)[:, :, None, :]

    def comb(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a1 * a2, a2 * b1 + b2

    a_sc, b_sc = jax.lax.associative_scan(comb, (Abar, Bx), axis=1)
    h = b_sc + a_sc * h0[:, None]                             # (B,c,d_in,st)
    y = jnp.einsum("bcds,bcs->bcd", h, Cm.astype(jnp.float32))
    return h[:, -1], y


def mamba_apply(p: Params, x: jnp.ndarray, cfg: ArchConfig, *,
                chunk: int = 256) -> jnp.ndarray:
    """Train/prefill. x (B,S,d) -> (B,S,d)."""
    B, S, d = x.shape
    d_in, dtr, st, cw = _mamba_dims(cfg)
    xz = dense(p["in_proj"], x)
    xi, z = jnp.split(xz, 2, axis=-1)                         # (B,S,d_in)
    xc = jax.nn.silu(_causal_conv(xi, p["conv_w"], p["conv_b"]))
    dbc = dense(p["x_proj"], xc)
    dt_r, Bm, Cm = jnp.split(dbc, [dtr, dtr + st], axis=-1)
    dt = jax.nn.softplus(dense(p["dt_proj"], dt_r).astype(jnp.float32))
    A = -jnp.exp(p["A_log"])                                  # (d_in, st)

    c = min(chunk, S)
    pad = -S % c
    if pad:
        xc_p = jnp.pad(xc, ((0, 0), (0, pad), (0, 0)))
        dt_p = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm_p = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm_p = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    else:
        xc_p, dt_p, Bm_p, Cm_p = xc, dt, Bm, Cm
    n = xc_p.shape[1] // c

    def step(h, inp):
        dt_i, B_i, C_i, x_i = inp
        h, y = _selective_scan_chunk(h, dt_i, B_i, C_i, A, x_i)
        return h, y

    reshape = lambda a: jnp.moveaxis(a.reshape(B, n, c, -1), 1, 0)
    h0 = jnp.zeros((B, d_in, st), jnp.float32)
    _, ys = jax.lax.scan(step, h0, (reshape(dt_p), reshape(Bm_p),
                                    reshape(Cm_p), reshape(xc_p)))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, n * c, d_in)[:, :S]
    y = y + p["D"] * xc.astype(jnp.float32)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    return dense(p["out_proj"], y)


def mamba_prefill(p: Params, x: jnp.ndarray, cfg: ArchConfig):
    """Returns (y, h_state (B,d_in,st) f32, conv_state (B,cw-1,d_in))."""
    B, S, d = x.shape
    d_in, dtr, st, cw = _mamba_dims(cfg)
    xz = dense(p["in_proj"], x)
    xi, z = jnp.split(xz, 2, axis=-1)
    conv_state = xi[:, S - (cw - 1):, :] if S >= cw - 1 else jnp.pad(
        xi, ((0, 0), (cw - 1 - S, 0), (0, 0)))
    xc = jax.nn.silu(_causal_conv(xi, p["conv_w"], p["conv_b"]))
    dbc = dense(p["x_proj"], xc)
    dt_r, Bm, Cm = jnp.split(dbc, [dtr, dtr + st], axis=-1)
    dt = jax.nn.softplus(dense(p["dt_proj"], dt_r).astype(jnp.float32))
    A = -jnp.exp(p["A_log"])
    c = min(256, S)
    pad = -S % c
    padf = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0))) if pad else a
    n = (S + pad) // c
    reshape = lambda a: jnp.moveaxis(padf(a).reshape(B, n, c, -1), 1, 0)

    def step(h, inp):
        dt_i, B_i, C_i, x_i = inp
        h, y = _selective_scan_chunk(h, dt_i, B_i, C_i, A, x_i)
        return h, y

    h0 = jnp.zeros((B, d_in, st), jnp.float32)
    h_last, ys = jax.lax.scan(step, h0, (reshape(dt), reshape(Bm),
                                         reshape(Cm), reshape(xc)))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, n * c, d_in)[:, :S]
    y = y + p["D"] * xc.astype(jnp.float32)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    return dense(p["out_proj"], y), h_last, conv_state


def mamba_decode(p: Params, x: jnp.ndarray, h: jnp.ndarray,
                 conv_state: jnp.ndarray, cfg: ArchConfig):
    """Single step. x (B,1,d); h (B,d_in,st) f32; conv_state (B,cw-1,d_in).
    Returns (y (B,1,d), h, conv_state)."""
    B = x.shape[0]
    d_in, dtr, st, cw = _mamba_dims(cfg)
    xz = dense(p["in_proj"], x)
    xi, z = jnp.split(xz, 2, axis=-1)                         # (B,1,d_in)
    window = jnp.concatenate([conv_state.astype(x.dtype), xi], axis=1)  # (B,cw,d_in)
    xc = jax.nn.silu(jnp.einsum("bcd,cd->bd", window, p["conv_w"]) + p["conv_b"])
    conv_state = window[:, 1:]
    dbc = dense(p["x_proj"], xc)
    dt_r, Bm, Cm = jnp.split(dbc, [dtr, dtr + st], axis=-1)
    dt = jax.nn.softplus(dense(p["dt_proj"], dt_r).astype(jnp.float32))  # (B,d_in)
    A = -jnp.exp(p["A_log"])
    Abar = jnp.exp(dt[..., None] * A)                          # (B,d_in,st)
    Bx = (dt * xc.astype(jnp.float32))[..., None] * Bm.astype(jnp.float32)[:, None, :]
    h = Abar * h + Bx
    y = jnp.einsum("bds,bs->bd", h, Cm.astype(jnp.float32))
    y = y + p["D"] * xc.astype(jnp.float32)
    y = (y * jax.nn.silu(z[:, 0].astype(jnp.float32))).astype(x.dtype)
    return dense(p["out_proj"], y)[:, None, :], h, conv_state


# ===================================================================== #
# RG-LRU (RecurrentGemma) recurrent block
# ===================================================================== #
_LRU_C = 8.0


def rglru_init(key, cfg: ArchConfig) -> Params:
    d = cfg.d_model
    w = cfg.hybrid.lru_width or d
    cw = cfg.hybrid.conv_width
    dt = cfg.jdtype
    ks = jax.random.split(key, 6)
    return {
        "in_x": dense_init(ks[0], d, w, dt),
        "in_gate": dense_init(ks[1], d, w, dt),
        "conv_w": (jax.random.normal(ks[2], (cw, w), jnp.float32)
                   / math.sqrt(cw)).astype(dt),
        "conv_b": jnp.zeros((w,), dt),
        "wa": dense_init(ks[3], w, w, dt, bias=True),          # recurrence gate
        "wx": dense_init(ks[4], w, w, dt, bias=True),          # input gate
        "lam": jnp.full((w,), 4.0, jnp.float32),               # Λ param
        "out": dense_init(ks[5], w, d, dt),
    }


def _rglru_scan(p, xc, h0, *, chunk=256):
    """xc (B,S,w) post-conv branch; h0 (B,w) f32. Returns (y, h_last)."""
    B, S, w = xc.shape
    r = jax.nn.sigmoid(dense(p["wa"], xc).astype(jnp.float32))
    i = jax.nn.sigmoid(dense(p["wx"], xc).astype(jnp.float32))
    log_a = -_LRU_C * jax.nn.softplus(p["lam"]) * r            # (B,S,w)
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) \
        * (i * xc.astype(jnp.float32))

    c = min(chunk, S)
    pad = -S % c
    padf = lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0))) if pad else t
    n = (S + pad) // c
    resh = lambda t: jnp.moveaxis(padf(t).reshape(B, n, c, w), 1, 0)

    def comb(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a1 * a2, a2 * b1 + b2

    def step(h, inp):
        a_i, g_i = inp
        a_sc, b_sc = jax.lax.associative_scan(comb, (a_i, g_i), axis=1)
        hc = b_sc + a_sc * h[:, None]
        return hc[:, -1], hc

    h_last, ys = jax.lax.scan(step, h0, (resh(a), resh(gated)))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, n * c, w)[:, :S]
    return y, h_last


def rglru_apply(p: Params, x: jnp.ndarray, cfg: ArchConfig,
                h0: Optional[jnp.ndarray] = None,
                conv_state: Optional[jnp.ndarray] = None,
                return_state: bool = False):
    """Full recurrent block: (gate branch) * RG-LRU(conv(x branch)) -> out."""
    B, S, _ = x.shape
    w = cfg.hybrid.lru_width or cfg.d_model
    cw = cfg.hybrid.conv_width
    xb = dense(p["in_x"], x)
    gate = jax.nn.gelu(dense(p["in_gate"], x))
    new_conv = xb[:, S - (cw - 1):, :] if S >= cw - 1 else jnp.pad(
        xb, ((0, 0), (cw - 1 - S, 0), (0, 0)))
    xc = _causal_conv(xb, p["conv_w"], p["conv_b"], state=conv_state)
    if h0 is None:
        h0 = jnp.zeros((B, w), jnp.float32)
    y, h_last = _rglru_scan(p, xc, h0)
    out = dense(p["out"], (y.astype(x.dtype) * gate))
    if return_state:
        return out, h_last, new_conv
    return out


def rglru_decode(p: Params, x: jnp.ndarray, h: jnp.ndarray,
                 conv_state: jnp.ndarray, cfg: ArchConfig):
    """x (B,1,d); h (B,w) f32; conv_state (B,cw-1,w)."""
    B = x.shape[0]
    xb = dense(p["in_x"], x)                                   # (B,1,w)
    gate = jax.nn.gelu(dense(p["in_gate"], x))
    window = jnp.concatenate([conv_state.astype(x.dtype), xb], axis=1)
    xc = jnp.einsum("bcw,cw->bw", window, p["conv_w"]) + p["conv_b"]
    conv_state = window[:, 1:]
    r = jax.nn.sigmoid(dense(p["wa"], xc).astype(jnp.float32))
    i = jax.nn.sigmoid(dense(p["wx"], xc).astype(jnp.float32))
    log_a = -_LRU_C * jax.nn.softplus(p["lam"]) * r
    a = jnp.exp(log_a)
    h = a * h + jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) \
        * (i * xc.astype(jnp.float32))
    out = dense(p["out"], (h[:, None].astype(x.dtype) * gate))
    return out, h, conv_state
