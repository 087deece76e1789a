"""GQA attention layer (qk-norm, QKV-bias, RoPE, sliding window) + KV caches.

The cache is a dict so the whole model state remains a plain pytree:
  full   : k/v of shape (B, S_max, Hkv*Dh), linear writes at position t
  window : k/v of shape (B, W, Hkv*Dh), ring-buffer writes at t % W
The KV heads share the last axis, the layout the decode kernel reads, so a
step neither relayouts nor copies the cache for it (with Hkv*Dh a multiple
of 128 the tiled layout also carries no padding, where (Hkv, Dh) = (2, 64)
would).  RoPE is applied before caching, so ring-buffer slot order is
irrelevant (attention is set-wise given positions are baked into k).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.kernels import ops
from repro.models import layers as L


def attn_init(key, cfg: ModelConfig, cross: bool = False):
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 6)
    p = {
        "wq": L.dense_init(ks[0], cfg.d_model, cfg.q_dim, dt, cfg.qkv_bias),
        "wk": L.dense_init(ks[1], cfg.d_model, cfg.kv_dim, dt, cfg.qkv_bias),
        "wv": L.dense_init(ks[2], cfg.d_model, cfg.kv_dim, dt, cfg.qkv_bias),
        "wo": L.dense_init(ks[3], cfg.q_dim, cfg.d_model, dt),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = L.rmsnorm_init(cfg.head_dim, dt)
        p["k_norm"] = L.rmsnorm_init(cfg.head_dim, dt)
    return p


def _project_qkv(p, cfg: ModelConfig, xq, xkv, positions_q, positions_kv,
                 use_rope: bool):
    b, sq, _ = xq.shape
    skv = xkv.shape[1]
    q = L.dense_apply(p["wq"], xq).reshape(b, sq, cfg.n_heads, cfg.head_dim)
    k = L.dense_apply(p["wk"], xkv).reshape(b, skv, cfg.n_kv_heads, cfg.head_dim)
    v = L.dense_apply(p["wv"], xkv).reshape(b, skv, cfg.n_kv_heads, cfg.head_dim)
    if "q_norm" in p:
        q = L.rmsnorm_apply(p["q_norm"], q, cfg.norm_eps)
        k = L.rmsnorm_apply(p["k_norm"], k, cfg.norm_eps)
    if use_rope:
        q = L.rope_apply(q, positions_q, cfg.rope_theta)
        k = L.rope_apply(k, positions_kv, cfg.rope_theta)
    return q, k, v


def attn_apply(p, cfg: ModelConfig, spec: LayerSpec, x, positions, *,
               causal=True, impl="reference", cu_seqlens=None,
               max_seqlen=None):
    """Full-sequence attention (training / prefill without cache).

    Packed mode (``cu_seqlens`` given): x is the (1, T, D) packed cohort,
    ``positions`` the within-sequence positions (RoPE restarts per
    sequence), and attention is block-diagonal over the ``cu_seqlens``
    segments via :func:`ops.varlen_mha` — padded-path parity to fp
    tolerance on identical logical inputs."""
    q, k, v = _project_qkv(p, cfg, x, x, positions, positions, use_rope=True)
    if cu_seqlens is not None:
        assert x.shape[0] == 1, f"packed cohort must be (1, T, D): {x.shape}"
        out = ops.varlen_mha(q[0], k[0], v[0], cu_seqlens, causal=causal,
                             window=spec.window, max_seqlen=max_seqlen,
                             impl=impl)[None]
    else:
        out = ops.mha(q, k, v, causal=causal, window=spec.window,
                      q_positions=positions, kv_positions=positions, impl=impl)
    return L.dense_apply(p["wo"], out.reshape(*x.shape[:2], cfg.q_dim))


def attn_apply_with_kv(p, cfg: ModelConfig, spec: LayerSpec, x, positions, *,
                       causal=True, impl="reference"):
    """Like attn_apply but also returns the roped k/v (for prefill caching)."""
    q, k, v = _project_qkv(p, cfg, x, x, positions, positions, use_rope=True)
    out = ops.mha(q, k, v, causal=causal, window=spec.window,
                  q_positions=positions, kv_positions=positions, impl=impl)
    y = L.dense_apply(p["wo"], out.reshape(*x.shape[:2], cfg.q_dim))
    return y, {"k": k, "v": v}


def cross_attn_apply(p, cfg: ModelConfig, x, enc_out=None, enc_kv=None,
                     impl="reference"):
    """Decoder cross-attention.  Computes K/V from ``enc_out`` or reuses a
    prefill-cached ``enc_kv`` (decode path)."""
    b, sq, _ = x.shape
    q = L.dense_apply(p["wq"], x).reshape(b, sq, cfg.n_heads, cfg.head_dim)
    if enc_kv is None:
        enc_kv = encode_cross_kv(p, cfg, enc_out)
    out = ops.mha(q, enc_kv["k"], enc_kv["v"], causal=False, window=None,
                  impl=impl)
    return L.dense_apply(p["wo"], out.reshape(b, sq, cfg.q_dim))


def encode_cross_kv(p, cfg: ModelConfig, enc_out):
    b, skv, _ = enc_out.shape
    k = L.dense_apply(p["wk"], enc_out).reshape(b, skv, cfg.n_kv_heads, cfg.head_dim)
    v = L.dense_apply(p["wv"], enc_out).reshape(b, skv, cfg.n_kv_heads, cfg.head_dim)
    return {"k": k, "v": v}


# ------------------------------------------------------------------ KV cache

def cache_init(cfg: ModelConfig, spec: LayerSpec, batch, max_len, dtype):
    cap = min(spec.window, max_len) if spec.window else max_len
    shape = (batch, cap, cfg.kv_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_spec(cfg: ModelConfig, spec: LayerSpec, batch, max_len, dtype):
    cap = min(spec.window, max_len) if spec.window else max_len
    sh = jax.ShapeDtypeStruct((batch, cap, cfg.kv_dim), dtype)
    return {"k": sh, "v": sh}


def prefill_into_cache(cache, spec: LayerSpec, k, v, seq_len: int):
    """Write a full prefill's roped k/v (B, S, Hkv, Dh) into the cache
    (ring for window)."""
    cap = cache["k"].shape[1]
    k, v = (a.reshape(*a.shape[:2], -1) for a in (k, v))
    if seq_len <= cap:
        # contiguous prefix: a static slice-update, not a gather/scatter
        return {
            "k": jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), 0, axis=1),
            "v": jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), 0, axis=1),
        }
    k_w, v_w = k[:, -cap:], v[:, -cap:]
    slots = (jnp.arange(seq_len - cap, seq_len)) % cap
    return {
        "k": cache["k"].at[:, slots].set(k_w.astype(cache["k"].dtype)),
        "v": cache["v"].at[:, slots].set(v_w.astype(cache["v"].dtype)),
    }


def paged_attn_decode_apply(p, cfg: ModelConfig, spec: LayerSpec, x, cache,
                            block_table, positions, *, impl="reference"):
    """One-token decode through a paged block-pool KV cache.

    x: (B, 1, D); cache: {"k"/"v": (N, bs, Hkv, Dh)} shared pools;
    block_table: (B, M) int32; positions: (B,) int32 per-row write position
    (= tokens already cached for that row — rows advance independently
    under continuous batching).  Returns (y, new_cache)."""
    b = x.shape[0]
    pos = positions[:, None]
    q, k, v = _project_qkv(p, cfg, x, x, pos, pos, use_rope=True)
    bs = cache["k"].shape[1]
    blk = block_table[jnp.arange(b), positions // bs]  # (B,) physical ids
    off = positions % bs
    new_cache = {
        "k": cache["k"].at[blk, off].set(k[:, 0].astype(cache["k"].dtype)),
        "v": cache["v"].at[blk, off].set(v[:, 0].astype(cache["v"].dtype)),
    }
    out = ops.paged_decode_mha(q[:, 0], new_cache["k"], new_cache["v"],
                               block_table, cache_len=positions + 1,
                               impl=impl)
    y = L.dense_apply(p["wo"], out.reshape(b, 1, cfg.q_dim).astype(x.dtype))
    return y, new_cache


def paged_attn_verify_apply(p, cfg: ModelConfig, spec: LayerSpec, x, cache,
                            block_table, positions, *, impl="reference"):
    """Multi-token (speculative verify) decode through the paged block pool.

    x: (B, K, D) — the spec window (last committed token + draft tokens);
    positions: (B, K) int32 absolute per-token positions, consecutive per
    row.  All K tokens' roped KV is scattered into the pool first (distinct
    (block, offset) slots per row — consecutive positions never collide),
    then query j attends every logical position <= positions[b, j].
    Returns (y, new_cache)."""
    b, kk, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x, x, positions, positions, use_rope=True)
    bs = cache["k"].shape[1]
    blk = block_table[jnp.arange(b)[:, None], positions // bs]  # (B, K)
    off = positions % bs
    new_cache = {
        "k": cache["k"].at[blk, off].set(k.astype(cache["k"].dtype)),
        "v": cache["v"].at[blk, off].set(v.astype(cache["v"].dtype)),
    }
    out = ops.paged_verify_mha(q, new_cache["k"], new_cache["v"], block_table,
                               q_positions=positions, impl=impl)
    y = L.dense_apply(p["wo"], out.reshape(b, kk, cfg.q_dim).astype(x.dtype))
    return y, new_cache


def ragged_attn_verify_apply(p, cfg: ModelConfig, spec: LayerSpec, x, cache,
                             positions, *, impl="reference"):
    """Multi-token (speculative verify) step over a sliding-window ring.

    Writing all K tokens into the ring *before* attending would let the
    late writes evict slots the early queries still need (K fresh tokens
    overwrite the K oldest ring entries, which sit inside the first
    query's window when the ring capacity equals the window).  So the ring
    is linearized instead: each ring slot is tagged with the logical
    position of the token it currently holds, the K new tokens are
    appended as extra keys, and one banded attention over explicit
    positions scores everything.  The ring is updated afterwards."""
    assert spec.window is not None, \
        "ragged verify is ring-cache only; use paged_attn_verify_apply"
    b, kk, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x, x, positions, positions, use_rope=True)
    cap = cache["k"].shape[1]
    assert kk <= cap, f"spec window {kk} exceeds ring capacity {cap}"
    p0 = positions[:, :1]  # (B, 1) position of the first new token
    s = jnp.arange(cap)[None, :]
    # latest logical position t < p0 with t % cap == s; < 0 => never written
    t = p0 - 1 - ((p0 - 1 - s) % cap)
    kv_pos = jnp.where(t >= 0, t, jnp.int32(2 ** 30))  # causal-masks unwritten
    keys = jnp.concatenate([cache["k"].astype(k.dtype), k], axis=1)
    vals = jnp.concatenate([cache["v"].astype(v.dtype), v], axis=1)
    kv_positions = jnp.concatenate([kv_pos, positions], axis=1)
    out = ops.mha(q, keys, vals, causal=True, window=spec.window,
                  q_positions=positions, kv_positions=kv_positions, impl=impl)
    rows = jnp.arange(b)[:, None]
    slot = positions % cap
    new_cache = {
        "k": cache["k"].at[rows, slot].set(k.astype(cache["k"].dtype)),
        "v": cache["v"].at[rows, slot].set(v.astype(cache["v"].dtype)),
    }
    y = L.dense_apply(p["wo"], out.reshape(b, kk, cfg.q_dim).astype(x.dtype))
    return y, new_cache


def ragged_attn_decode_apply(p, cfg: ModelConfig, spec: LayerSpec, x, cache,
                             positions, *, impl="reference"):
    """Per-row-position variant of :func:`attn_decode_apply` for
    sliding-window ring caches: rows write at their own ``positions[b]``
    instead of one shared scalar ``t`` (continuous batching).  Window
    layers are already O(window) per row, so paging buys nothing there;
    full-attention layers must go through
    :func:`paged_attn_decode_apply` instead."""
    assert spec.window is not None, \
        "ragged decode is ring-cache only; use paged_attn_decode_apply"
    b = x.shape[0]
    pos = positions[:, None]
    q, k, v = _project_qkv(p, cfg, x, x, pos, pos, use_rope=True)
    cap = cache["k"].shape[1]
    slot = positions % cap
    rows = jnp.arange(b)
    new_cache = {
        "k": cache["k"].at[rows, slot].set(k[:, 0].astype(cache["k"].dtype)),
        "v": cache["v"].at[rows, slot].set(v[:, 0].astype(cache["v"].dtype)),
    }
    out = ops.decode_mha(q[:, 0], new_cache["k"], new_cache["v"],
                         cache_len=positions + 1, window=spec.window,
                         impl=impl)
    y = L.dense_apply(p["wo"], out.reshape(b, 1, cfg.q_dim).astype(x.dtype))
    return y, new_cache


def attn_decode_apply(p, cfg: ModelConfig, spec: LayerSpec, x, cache, t,
                      layer, *, impl="reference"):
    """One-token decode on layer ``layer`` of a scan group's stacked cache
    {"k"/"v": (L, B, C, Hkv*Dh)}.  x: (B, 1, D); t: scalar int32 position.
    The new token's k/v go in as one (1, B, 1, Hkv*Dh) update at
    (layer, 0, slot), which XLA makes in place on the carried stack, and
    the attention reads the layer where it lies.  Returns (y, new_cache)."""
    b = x.shape[0]
    positions = jnp.full((b, 1), t, dtype=jnp.int32)
    q, k, v = _project_qkv(p, cfg, x, x, positions, positions, use_rope=True)
    cap = cache["k"].shape[2]
    slot = (t % cap) if spec.window else t
    new_cache = {
        name: jax.lax.dynamic_update_slice(
            cache[name], new.reshape(1, b, 1, -1).astype(cache[name].dtype),
            (layer, 0, slot, 0))
        for name, new in (("k", k), ("v", v))}
    cache_len = jnp.full((b,), t + 1, dtype=jnp.int32)
    out = ops.decode_mha(q[:, 0], new_cache["k"], new_cache["v"],
                         cache_len=cache_len, window=spec.window, layer=layer,
                         impl=impl)
    y = L.dense_apply(p["wo"], out.reshape(b, 1, cfg.q_dim).astype(x.dtype))
    return y, new_cache
