"""Stacked-transformer assembly for every architecture family.

A model is a list of scan groups; each group is a superblock (tuple of
LayerSpecs) whose params are stacked over ``n`` repeats and driven by
``lax.scan``.  Three execution paths share the same params:

  * ``stack_apply``  — full-sequence forward (training / scoring)
  * ``stack_prefill``— full-sequence forward that also emits decode caches
  * ``stack_decode`` — single-token step carrying caches/recurrent states

Blocks: mixer (attention / RG-LRU / SSD) + optional FFN (gated MLP or MoE),
with pre-norms; decoder blocks of enc-dec models add cross-attention.
Attention is GQA (``models/attention.py``) or, with ``cfg.kv_lora_rank``,
multi-head latent attention (``models/mla.py``) over a latent cache.

The three paths also return the routing counters of every MoE layer, in
layer order: ``routed``, ``held`` and ``held_max``, each (L,)
(``moe.moe_apply``); an empty dict for models without MoE layers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, LRU, SSM, LayerSpec, ModelConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as M
from repro.models import rglru as R
from repro.models import ssm as S
from repro.parallel import ctx


def groups_of(cfg: ModelConfig) -> list[tuple[tuple[LayerSpec, ...], int]]:
    gs = [(cfg.lead, 1)] if cfg.lead else []
    gs.append((cfg.superblock, cfg.n_superblocks))
    if cfg.tail:
        gs.append((cfg.tail, 1))
    return gs


def _is_moe(cfg: ModelConfig, spec: LayerSpec) -> bool:
    return (spec.has_ffn and cfg.ffn_kind == "moe" and not spec.dense_ffn)


def _layer_counts(ys):
    """A scan's per-block counters ([{(n, ...)}] over the blocks of the
    pattern that have them) as one tree in layer order."""
    if not ys:
        return {}
    return jax.tree.map(
        lambda *a: jnp.stack(a, 1).reshape(-1, *a[0].shape[1:]), *ys)


def _merge_counts(per_group):
    """Concatenate the groups' counters ({} where a group has none)."""
    cs = [c for c in per_group if c]
    return jax.tree.map(lambda *a: jnp.concatenate(a, 0), *cs) if cs else {}


# ------------------------------------------------------------------- blocks

def block_init(key, cfg: ModelConfig, spec: LayerSpec, cross: bool = False):
    ks = jax.random.split(key, 6)
    dt = jnp.dtype(cfg.dtype)
    p = {"ln1": L.rmsnorm_init(cfg.d_model, dt)}
    if spec.kind == ATTN and cfg.is_mla:
        p["mixer"] = MLA.mla_init(ks[0], cfg)
    elif spec.kind == ATTN:
        p["mixer"] = A.attn_init(ks[0], cfg)
    elif spec.kind == LRU:
        p["mixer"] = R.lru_init(ks[0], cfg)
    else:
        p["mixer"] = S.ssm_init(ks[0], cfg)
    if cross:
        p["lnx"] = L.rmsnorm_init(cfg.d_model, dt)
        p["xattn"] = A.attn_init(ks[1], cfg, cross=True)
    if spec.has_ffn and cfg.ffn_kind != "none":
        p["ln2"] = L.rmsnorm_init(cfg.d_model, dt)
        p["ffn"] = (M.moe_init(ks[2], cfg) if _is_moe(cfg, spec)
                    else L.mlp_init(ks[2], cfg))
    return p


# a dense FFN over more than this many hidden activations (tokens x d_ff)
# runs in token chunks, each rematerialised in the backward pass, so that
# its working set is a chunk's
FFN_CHUNK_ACTIVATIONS = 1 << 26


def _mlp(p, cfg, h):
    """The dense FFN, in the fewest equal token chunks of at most
    FFN_CHUNK_ACTIVATIONS (``layers.even_chunks``); a token's result does
    not depend on its chunk."""
    b, s, d = h.shape
    t = b * s
    c = L.even_chunks(t, cfg.d_ff, FFN_CHUNK_ACTIVATIONS)
    if c == 1:
        return L.mlp_apply(p, cfg, h)
    y = jax.lax.map(jax.checkpoint(lambda x: L.mlp_apply(p, cfg, x)),
                    h.reshape(c, t // c, d))
    return y.reshape(b, s, d)


def _ffn(p, cfg, spec, x, *, impl="reference", want_aux=True):
    """Returns (x, aux, routing counters ({} without MoE))."""
    if "ffn" not in p:
        return x, 0.0, {}
    h = L.rmsnorm_apply(p["ln2"], x, cfg.norm_eps)
    if _is_moe(cfg, spec):
        y, aux, counts = M.moe_apply(p["ffn"], cfg, h, impl=impl,
                                     want_aux=want_aux)
        return x + y, aux, counts
    return x + _mlp(p["ffn"], cfg, h), 0.0, {}


def block_apply(p, cfg, spec, x, positions, *, causal=True, impl="reference",
                enc_out=None, want_state=False, cu_seqlens=None,
                max_seqlen=None):
    """Full-sequence block.  Returns (x, aux_loss, state_or_None, routing
    counters ({} without MoE)).

    Packed mode (``cu_seqlens`` given): attention goes block-diagonal over
    the packed segments; norms/FFN/MoE are per-token and need no change.
    Recurrent mixers (LRU/SSD) scan the raw token axis and would leak
    state across sequence boundaries, so they reject packed cohorts."""
    if cu_seqlens is not None and spec.kind != ATTN:
        raise NotImplementedError(
            f"packed training is attention-only; got mixer kind {spec.kind}")
    h = L.rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
    state = None
    if spec.kind == ATTN and cfg.is_mla:
        if cu_seqlens is not None:
            raise NotImplementedError("packed training has no MLA path")
        out = MLA.mla_apply(p["mixer"], cfg, h, positions, causal=causal,
                            impl=impl, want_latent=want_state)
        y, state = out if want_state else (out, None)
    elif spec.kind == ATTN:
        if want_state:
            y, kv = A.attn_apply_with_kv(p["mixer"], cfg, spec, h, positions,
                                         causal=causal, impl=impl)
            state = kv
        else:
            y = A.attn_apply(p["mixer"], cfg, spec, h, positions,
                             causal=causal, impl=impl,
                             cu_seqlens=cu_seqlens, max_seqlen=max_seqlen)
    elif spec.kind == LRU:
        out = R.lru_apply(p["mixer"], cfg, h, impl=impl, return_state=want_state)
        y, state = out if want_state else (out, None)
    else:
        out = S.ssm_apply(p["mixer"], cfg, h, impl=impl, return_state=want_state)
        y, state = out if want_state else (out, None)
    x = x + y
    if enc_out is not None:
        hx = L.rmsnorm_apply(p["lnx"], x, cfg.norm_eps)
        x = x + A.cross_attn_apply(p["xattn"], cfg, hx, enc_out, impl=impl)
    x, aux, counts = _ffn(p, cfg, spec, x, impl=impl)
    return x, aux, state, counts


def _layer_of(stack, layer):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, False), stack)


def _set_layer(stack, new, layer):
    return jax.tree.map(
        lambda a, u: jax.lax.dynamic_update_index_in_dim(
            a, u.astype(a.dtype), layer, 0), stack, new)


def block_decode(p, cfg, spec, x, cache, t, layer, *, impl="reference",
                 cross=False):
    """Single-token block step on layer ``layer`` of a scan group's stacked
    cache (every leaf (L, ...)).  Returns (x, new_cache, routing counters):
    the stack with this layer's entries updated in place -- one token of
    K/V for attention (of latent for MLA), the small recurrent state for
    LRU/SSM; cross-attention K/V is read."""
    mixer_cache = cache["self"] if cross else cache
    h = L.rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
    if spec.kind == ATTN and cfg.is_mla:
        y, new_mixer = MLA.mla_decode_apply(p["mixer"], cfg, h, mixer_cache,
                                            t, layer)
    elif spec.kind == ATTN:
        y, new_mixer = A.attn_decode_apply(p["mixer"], cfg, spec, h,
                                           mixer_cache, t, layer, impl=impl)
    else:
        step = R.lru_decode_apply if spec.kind == LRU else S.ssm_decode_apply
        y, state = step(p["mixer"], cfg, h, _layer_of(mixer_cache, layer))
        new_mixer = _set_layer(mixer_cache, state, layer)
    x = x + y
    if cross:
        hx = L.rmsnorm_apply(p["lnx"], x, cfg.norm_eps)
        x = x + A.cross_attn_apply(p["xattn"], cfg, hx,
                                   enc_kv=_layer_of(cache["xkv"], layer),
                                   impl=impl)
    x, _, counts = _ffn(p, cfg, spec, x, impl=impl, want_aux=False)
    new_cache = {"self": new_mixer, "xkv": cache["xkv"]} if cross else new_mixer
    return x, new_cache, counts


def block_paged_decode(p, cfg, spec, x, cache, block_table, positions, *,
                       impl="reference"):
    """Single-token block step with per-row positions over paged caches.

    Full-attention layers write/read through the shared block pool via
    ``block_table``; window layers use their per-slot ring buffers;
    recurrent mixers are position-free.  Returns (x, new_cache)."""
    if cfg.is_mla:
        raise NotImplementedError("paged decode has no MLA path")
    h = L.rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
    if spec.kind == ATTN:
        if spec.window is None:
            y, new_cache = A.paged_attn_decode_apply(
                p["mixer"], cfg, spec, h, cache, block_table, positions,
                impl=impl)
        else:
            y, new_cache = A.ragged_attn_decode_apply(
                p["mixer"], cfg, spec, h, cache, positions, impl=impl)
    elif spec.kind == LRU:
        y, new_cache = R.lru_decode_apply(p["mixer"], cfg, h, cache)
    else:
        y, new_cache = S.ssm_decode_apply(p["mixer"], cfg, h, cache)
    x = x + y
    x, _, _ = _ffn(p, cfg, spec, x, impl=impl, want_aux=False)
    return x, new_cache


def block_paged_verify(p, cfg, spec, x, cache, block_table, positions, *,
                       impl="reference"):
    """K-token speculative verify block step.  x: (B, K, D); positions:
    (B, K) per-token absolute positions.  Attention-only: recurrent mixers
    would need per-step state rollback on draft rejection, so they are
    rejected here (the spec-decode entry points gate on this upfront).
    Returns (x, new_cache)."""
    if spec.kind != ATTN or cfg.is_mla:
        raise NotImplementedError(
            f"speculative verify is GQA-attention-only; got mixer kind "
            f"{spec.kind}")
    h = L.rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
    if spec.window is None:
        y, new_cache = A.paged_attn_verify_apply(
            p["mixer"], cfg, spec, h, cache, block_table, positions,
            impl=impl)
    else:
        y, new_cache = A.ragged_attn_verify_apply(
            p["mixer"], cfg, spec, h, cache, positions, impl=impl)
    x = x + y
    x, _, _ = _ffn(p, cfg, spec, x, impl=impl, want_aux=False)
    return x, new_cache


# -------------------------------------------------------------- scan groups

def group_init(key, cfg: ModelConfig, specs, n: int, cross: bool = False):
    def init_one(k):
        kk = jax.random.split(k, len(specs))
        return {f"b{i}": block_init(kk[i], cfg, s, cross)
                for i, s in enumerate(specs)}
    return jax.vmap(init_one)(jax.random.split(key, n))


def stack_init(key, cfg: ModelConfig, cross: bool = False):
    gs = groups_of(cfg)
    keys = jax.random.split(key, len(gs))
    return [group_init(k, cfg, specs, n, cross)
            for k, (specs, n) in zip(keys, gs)]


def stack_apply(groups_params, cfg: ModelConfig, x, positions, *, causal=True,
                impl="reference", enc_out=None, remat=True, cu_seqlens=None,
                max_seqlen=None):
    """Returns (x, aux, the MoE layers' routing counters)."""
    aux_total = jnp.zeros((), jnp.float32)
    per_group = []
    for (specs, n), gp in zip(groups_of(cfg), groups_params):
        def body(carry, layer_p, specs=specs):
            xc, aux = carry
            xc = ctx.constrain(xc, ctx.BATCH, None, None)
            ys = []
            for i, spec in enumerate(specs):
                xc, a, _, c = block_apply(
                    layer_p[f"b{i}"], cfg, spec, xc, positions,
                    causal=causal, impl=impl, enc_out=enc_out,
                    cu_seqlens=cu_seqlens, max_seqlen=max_seqlen)
                ys += [c] if c else []
                aux = aux + a
            return (xc, aux), ys
        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        (x, aux_total), ys = jax.lax.scan(body, (x, aux_total), gp)
        per_group.append(_layer_counts(ys))
    return x, aux_total, _merge_counts(per_group)


def group_cache_init(cfg: ModelConfig, specs, n, batch, max_len, dtype,
                     cross=False, enc_len=None):
    def one(spec):
        if spec.kind == ATTN and cfg.is_mla:
            c = MLA.cache_init(cfg, batch, max_len, dtype)
        elif spec.kind == ATTN:
            c = A.cache_init(cfg, spec, batch, max_len, dtype)
        elif spec.kind == LRU:
            c = R.lru_state_init(cfg, batch, dtype)
        else:
            c = S.ssm_state_init(cfg, batch, dtype)
        if cross:
            kv = jnp.zeros((batch, enc_len, cfg.n_kv_heads, cfg.head_dim), dtype)
            return {"self": c, "xkv": {"k": kv, "v": kv}}
        return c
    block = {f"b{i}": one(s) for i, s in enumerate(specs)}
    return jax.tree.map(lambda a: jnp.zeros((n,) + a.shape, a.dtype), block)


def cache_init(cfg: ModelConfig, batch, max_len, dtype, cross=False,
               enc_len=None):
    return [group_cache_init(cfg, specs, n, batch, max_len, dtype, cross,
                             enc_len)
            for specs, n in groups_of(cfg)]


def stack_prefill(groups_params, cfg: ModelConfig, x, positions, caches, *,
                  impl="reference", enc_out=None):
    """Full forward that fills decode caches.  ``caches`` from cache_init.
    A serving path: skips the (dead) MoE aux-loss work, returns (x, caches,
    routing counters)."""
    seq_len = x.shape[1]
    new_caches, per_group = [], []
    for (specs, n), gp, gc in zip(groups_of(cfg), groups_params, caches):
        def body(xc, inp, specs=specs):
            xc = ctx.constrain(xc, ctx.BATCH, None, None)
            layer_p, cache = inp
            out_cache, ys = {}, []
            for i, spec in enumerate(specs):
                p = layer_p[f"b{i}"]
                bc = cache[f"b{i}"]
                mixer_cache = bc["self"] if enc_out is not None else bc
                h = L.rmsnorm_apply(p["ln1"], xc, cfg.norm_eps)
                if spec.kind == ATTN and cfg.is_mla:
                    y, lat = MLA.mla_apply(p["mixer"], cfg, h, positions,
                                           impl=impl, want_latent=True)
                    new_mixer = MLA.prefill_into_cache(mixer_cache, lat)
                elif spec.kind == ATTN:
                    y, kv = A.attn_apply_with_kv(p["mixer"], cfg, spec, h,
                                                 positions, causal=True,
                                                 impl=impl)
                    new_mixer = A.prefill_into_cache(
                        mixer_cache, spec, kv["k"], kv["v"], seq_len)
                elif spec.kind == LRU:
                    y, new_mixer = R.lru_apply(p["mixer"], cfg, h, impl=impl,
                                               return_state=True)
                else:
                    y, new_mixer = S.ssm_apply(p["mixer"], cfg, h, impl=impl,
                                               return_state=True)
                xc = xc + y
                if enc_out is not None:
                    hx = L.rmsnorm_apply(p["lnx"], xc, cfg.norm_eps)
                    xkv = A.encode_cross_kv(p["xattn"], cfg, enc_out)
                    xc = xc + A.cross_attn_apply(p["xattn"], cfg, hx,
                                                 enc_kv=xkv, impl=impl)
                    out_cache[f"b{i}"] = {"self": new_mixer,
                                          "xkv": jax.tree.map(
                                              lambda a: a.astype(cfg.dtype), xkv)}
                else:
                    out_cache[f"b{i}"] = new_mixer
                xc, _, c = _ffn(p, cfg, spec, xc, impl=impl, want_aux=False)
                ys += [c] if c else []
            return xc, (out_cache, ys)
        x, (nc, ys) = jax.lax.scan(body, x, (gp, gc))
        new_caches.append(nc)
        per_group.append(_layer_counts(ys))
    return x, new_caches, _merge_counts(per_group)


def stack_paged_decode(groups_params, cfg: ModelConfig, x, caches,
                       block_table, positions, *, impl="reference"):
    """x: (B, 1, D); block_table: (B, M) int32; positions: (B,) int32
    per-row token position.  Returns (x, new_caches)."""
    new_caches = []
    for (specs, n), gp, gc in zip(groups_of(cfg), groups_params, caches):
        def body(xc, inp, specs=specs):
            xc = ctx.constrain(xc, ctx.BATCH, None, None)
            layer_p, cache = inp
            out_cache = {}
            for i, spec in enumerate(specs):
                xc, out_cache[f"b{i}"] = block_paged_decode(
                    layer_p[f"b{i}"], cfg, spec, xc, cache[f"b{i}"],
                    block_table, positions, impl=impl)
            return xc, out_cache
        x, nc = jax.lax.scan(body, x, (gp, gc))
        new_caches.append(nc)
    return x, new_caches


def stack_paged_verify(groups_params, cfg: ModelConfig, x, caches,
                       block_table, positions, *, impl="reference"):
    """x: (B, K, D) — one speculative verify window per row; block_table:
    (B, M) int32; positions: (B, K) int32 per-token positions.  Returns
    (x, new_caches)."""
    new_caches = []
    for (specs, n), gp, gc in zip(groups_of(cfg), groups_params, caches):
        def body(xc, inp, specs=specs):
            xc = ctx.constrain(xc, ctx.BATCH, None, None)
            layer_p, cache = inp
            out_cache = {}
            for i, spec in enumerate(specs):
                xc, out_cache[f"b{i}"] = block_paged_verify(
                    layer_p[f"b{i}"], cfg, spec, xc, cache[f"b{i}"],
                    block_table, positions, impl=impl)
            return xc, out_cache
        x, nc = jax.lax.scan(body, x, (gp, gc))
        new_caches.append(nc)
    return x, new_caches


def stack_decode(groups_params, cfg: ModelConfig, x, caches, t, *,
                 impl="reference", cross=False):
    """x: (B, 1, D); t: scalar position.  Returns (x, new_caches, the step's
    routing counters).

    The layer scan takes the params as xs and carries each group's stacked
    caches with the layer index: every layer writes its token in place and
    reads its cache where it lies, so no step copies a layer's cache out of
    the stack or back into it."""
    new_caches, per_group = [], []
    for (specs, n), gp, gc in zip(groups_of(cfg), groups_params, caches):
        def body(carry, layer_p, specs=specs):
            xc, cache, layer = carry
            xc = ctx.constrain(xc, ctx.BATCH, None, None)
            cache = dict(cache)
            ys = []
            for i, spec in enumerate(specs):
                xc, cache[f"b{i}"], c = block_decode(
                    layer_p[f"b{i}"], cfg, spec, xc, cache[f"b{i}"], t, layer,
                    impl=impl, cross=cross)
                ys += [c] if c else []
            return (xc, cache, layer + 1), ys
        (x, gc, _), ys = jax.lax.scan(body, (x, gc, jnp.int32(0)), gp)
        new_caches.append(gc)
        per_group.append(_layer_counts(ys))
    return x, new_caches, _merge_counts(per_group)
