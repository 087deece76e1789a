"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434) and its cache.

Per token: q = x W_q, split per head into q_nope (qk_nope_head_dim) and
q_pe (qk_rope_head_dim); [c | k_pe] = x W_kv_a with c the kv_lora_rank-wide
latent, RMS-normed, and k_pe one RoPE key shared by every head; per head
[k_nope | v] = c W_kv_b.  Scores q_nope.k_nope + q_pe.k_pe at
head_dim**-0.5 times YaRN's temperature squared.

Two forms of the same attention:
  * expanded (training, scoring, prefill): K and V of every head are built
    from the latent and go through ``ops.mha``;
  * absorbed (decode): W_kv_b's key half is folded into the query and its
    value half applied after the weighted sum, so a step reads only the
    latent cache and never builds a head's K or V.

The cache holds [c | k_pe] (kv_lora_rank + qk_rope_head_dim values a token,
k_pe roped) in one (B, C, r + dr) array a layer, stacked per scan group as
(L, B, C, r + dr), and a decode step writes its token in place.  RoPE uses
the rotate-half pairing (i, i + dr/2) over the rope dims; DeepSeek's
published code de-interleaves them first, which differs only by a fixed
permutation of the rope columns of W_q and W_kv_a.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import layers as L

NEG_INF = -2.0**30
# the reference tier's expanded attention materialises every head's fp32
# scores: a long cohort attends in batch chunks of at most this many score
# bytes, each rematerialised in the backward pass
SCORE_CHUNK_BYTES = 1 << 27


def mla_init(key, cfg: ModelConfig):
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    return {
        "wq": L.dense_init(ks[0], d, h * cfg.head_dim, dt),
        "wkv_a": L.dense_init(ks[1], d, r + cfg.qk_rope_head_dim, dt),
        "kv_norm": L.rmsnorm_init(r, dt),
        "wkv_b": L.dense_init(ks[2], r, h * (cfg.qk_nope_head_dim
                                             + cfg.v_head_dim), dt),
        "wo": L.dense_init(ks[3], h * cfg.v_head_dim, d, dt),
    }


def softmax_scale(cfg: ModelConfig) -> float:
    """head_dim**-0.5, times YaRN's temperature squared when scaled."""
    m = L.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) \
        if cfg.yarn_mscale_all_dim else 1.0
    return cfg.head_dim ** -0.5 * m * m


def _rope(x, positions, cfg: ModelConfig):
    return L.rope_apply(x, positions, cfg.rope_theta,
                        freqs=L.rope_freqs(cfg, cfg.qk_rope_head_dim))


def _query(p, cfg: ModelConfig, x, positions):
    """(q_nope (B, S, H, dn), roped q_pe (B, S, H, dr))."""
    b, s, _ = x.shape
    q = L.dense_apply(p["wq"], x).reshape(b, s, cfg.n_heads, cfg.head_dim)
    dn = cfg.qk_nope_head_dim
    return q[..., :dn], _rope(q[..., dn:], positions, cfg)


def latent(p, cfg: ModelConfig, x, positions):
    """The cached form of each token: [normed c | roped k_pe], (B, S,
    r + dr)."""
    r = cfg.kv_lora_rank
    kv = L.dense_apply(p["wkv_a"], x)
    c = L.rmsnorm_apply(p["kv_norm"], kv[..., :r], cfg.norm_eps)
    k_pe = _rope(kv[..., None, r:], positions, cfg)[:, :, 0]
    return jnp.concatenate([c, k_pe], axis=-1)


def mla_apply(p, cfg: ModelConfig, x, positions, *, causal=True,
              impl="reference", want_latent=False):
    """Expanded MLA over a full sequence.  x: (B, S, D).  Returns y, or
    (y, latent) with ``want_latent`` (prefill fills the cache with it)."""
    b, s, _ = x.shape
    h, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    with jax.named_scope("mla"):
        q_nope, q_pe = _query(p, cfg, x, positions)
        lat = latent(p, cfg, x, positions)
        kv = L.dense_apply(p["wkv_b"], lat[..., :r]).reshape(b, s, h, dn + dv)
        k_pe = jnp.broadcast_to(lat[..., None, r:],
                                (b, s, h, cfg.qk_rope_head_dim))
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        k = jnp.concatenate([kv[..., :dn], k_pe.astype(kv.dtype)], axis=-1)
        out = _attend(cfg, q, k, kv[..., dn:], positions, causal, impl)
        y = L.dense_apply(p["wo"], out.reshape(b, s, h * dv))
    return (y, lat) if want_latent else y


def _attend(cfg: ModelConfig, q, k, v, positions, causal, impl):
    """``ops.mha`` at the MLA scale; the reference tier over the fewest
    equal batch chunks of at most SCORE_CHUNK_BYTES of scores
    (``layers.even_chunks``), a row's result not depending on its chunk."""
    b, s, h, _ = q.shape

    def attend(q, k, v):
        return ops.mha(q, k, v, causal=causal, q_positions=positions,
                       kv_positions=positions, scale=softmax_scale(cfg),
                       impl=impl)
    c = (L.even_chunks(b, h * s * s * 4, SCORE_CHUNK_BYTES)
         if impl == "reference" else 1)
    if c == 1:
        return attend(q, k, v)
    out = jax.lax.map(jax.checkpoint(lambda a: attend(*a)), tuple(
        x.reshape(c, b // c, *x.shape[1:]) for x in (q, k, v)))
    return out.reshape(b, *out.shape[2:])


# ------------------------------------------------------------------ cache

def cache_init(cfg: ModelConfig, batch, max_len, dtype):
    width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    return {"latent": jnp.zeros((batch, max_len, width), dtype)}


def prefill_into_cache(cache, lat):
    """Write a prefill's latents (B, S, r + dr) at positions 0..S-1."""
    return {"latent": jax.lax.dynamic_update_slice_in_dim(
        cache["latent"], lat.astype(cache["latent"].dtype), 0, axis=1)}


def mla_decode_apply(p, cfg: ModelConfig, x, cache, t, layer):
    """Absorbed MLA for one token on layer ``layer`` of a scan group's
    stacked cache {"latent": (L, B, C, r + dr)}.  x: (B, 1, D); t: scalar
    int32 position.  The token's latent goes in as one (1, B, 1, r + dr)
    update, made in place on the carried stack; the scores and the
    weighted sum read the layer's latents where they lie.  Returns
    (y, new_cache)."""
    b = x.shape[0]
    h, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    f32 = jnp.float32
    with jax.named_scope("mla"):
        positions = jnp.full((b, 1), t, dtype=jnp.int32)
        q_nope, q_pe = _query(p, cfg, x, positions)
        new = latent(p, cfg, x, positions)
        stack = jax.lax.dynamic_update_slice(
            cache["latent"],
            new.reshape(1, b, 1, -1).astype(cache["latent"].dtype),
            (layer, 0, t, 0))
        lat = jax.lax.dynamic_index_in_dim(stack, layer, 0, False)  # (B,C,W)
        w_kv_b = p["wkv_b"]["w"].reshape(r, h, dn + dv)
        # the key half of W_kv_b folded into the query: q_lat.c = q.k_nope
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_kv_b[..., :dn],
                           preferred_element_type=f32).astype(lat.dtype)
        q_all = jnp.concatenate([q_lat, q_pe[:, 0].astype(lat.dtype)], -1)
        # the cache as the left operand, contracted over its minor axis, and
        # on the right in the weighted sum, contracted over its major one:
        # both read it in the layout it is stored in
        s = jnp.einsum("bcw,bhw->bhc", lat, q_all,
                       preferred_element_type=f32) * softmax_scale(cfg)
        valid = jnp.arange(lat.shape[1]) <= t
        s = jnp.where(valid[None, None], s, NEG_INF)
        probs = jax.nn.softmax(s, axis=-1)
        # the weighted sum over whole cached rows; its k_pe columns are
        # dropped after the sum, so the cache is read as it lies
        o_lat = jnp.einsum("bhc,bcw->bhw", probs.astype(lat.dtype), lat,
                           preferred_element_type=f32)[..., :r]
        o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(lat.dtype),
                       w_kv_b[..., dn:], preferred_element_type=f32)
        y = L.dense_apply(p["wo"], o.reshape(b, 1, h * dv).astype(x.dtype))
    return y, {"latent": stack}
