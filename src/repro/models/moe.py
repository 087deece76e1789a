"""Top-k MoE FFN with cohort-independent dropless dispatch (EP friendly).

``cfg.moe_dispatch`` selects the dispatch scheme:

* ``"dropless"`` (default) — sort-by-expert with ragged per-expert group
  offsets feeding a grouped expert GEMM (``ops.grouped_ffn``) over the *real*
  token count.  No capacity buffer, no drops: every row runs through exactly
  its own top-k experts with weights renormalized over that row's own router
  output, so a token routes identically — and its FFN result agrees to fp
  tolerance (only reduction-grouping ulps differ between cohort shapes) —
  whether it is computed in the training forward, a prefill, or a
  single-token decode step (the rollout / trainer logprob consistency PPO
  assumes).  It is also a decode *speed* win:
  the capacity path pads a t-token step to ``E × max(8, capacity)`` rows.
* ``"capacity"`` — the classic (E, C, D) capacity-drop scheme, kept for
  training-parity experiments.  Capacity scales with the cohort's token
  count and drop rank spans the flat batch-major cohort, so routing is
  cohort-*dependent*.  Dropped tokens fall back to the residual path, with
  combine weights renormalized over the experts actually kept.

Both paths accumulate the combine in fp32 and cast to the model dtype once
at the end.  Arctic's dense-residual MLP rides alongside either scheme.

DeepSeekMoE (dropless only): ``cfg.norm_topk_prob=False`` keeps the softmax
probabilities of the top-k as the combine weights, and a shared expert
(a SwiGLU of ``cfg.shared_d_ff``) sees every token.  Under expert
parallelism the layer holds ``cfg.local_experts`` experts from
``cfg.expert_offset``: the router still scores all ``cfg.n_experts``, the
assignments to held experts go through the grouped GEMM, and the rest add
nothing here (they are other chips' part of the result).

``moe_apply`` also returns the routing counters of the pass, three int32
scalars that add up over passes: ``routed``, the assignments made (T·k);
``held``, those that landed on a held expert; and ``held_max``, those of
the busiest held expert.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import layers as L

CAPACITY_FACTOR = 1.25
# the dropless dispatch handles at most this many tokens at a time: it
# gathers k rows a token, so a training cohort's dispatch and its gradient
# are bounded by a chunk's rows, not the cohort's
DISPATCH_CHUNK = 1024


def moe_init(key, cfg: ModelConfig):
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 6)
    e, d, f = cfg.local_experts, cfg.d_model, cfg.expert_d_ff
    scale = d ** -0.5
    p = {
        "router": L.dense_init(ks[0], d, cfg.n_experts, jnp.float32),
        "w_gate": L.truncated_normal(ks[1], (e, d, f), dt, scale),
        "w_in": L.truncated_normal(ks[2], (e, d, f), dt, scale),
        "w_out": L.truncated_normal(ks[3], (e, f, d), dt, f ** -0.5),
    }
    if cfg.dense_residual_ffn:
        p["dense"] = L.mlp_init(ks[4], cfg)
    if cfg.n_shared_experts:
        p["shared"] = L.mlp_init(ks[5], cfg, d_ff=cfg.shared_d_ff)
    return p


def capacity(n_tokens: int, cfg: ModelConfig) -> int:
    c = int(n_tokens * cfg.top_k * CAPACITY_FACTOR / cfg.n_experts)
    return max(8, min(n_tokens, c))


def _router(p, cfg: ModelConfig, xf):
    """(T, D) -> (probs (T, E) f32, top_w (T, K) f32, top_i (T, K) i32)."""
    logits = L.dense_apply(p["router"], xf.astype(jnp.float32))  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, cfg.top_k)
    return probs, top_w, top_i


def _aux_loss(probs, top_i, e: int):
    """Switch-style load-balance loss: E * sum_e f_e * p_e."""
    tk = top_i.size
    me = probs.mean(axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[top_i.reshape(-1)].add(
        jnp.ones((tk,), jnp.float32)) / tk
    return e * jnp.sum(me * ce)


def _sort_by_expert(top_i, t: int, k: int):
    """Flatten (T, K) assignments and stably sort by expert id.
    Returns (order, se, st): sorted flat indices, expert ids, token ids."""
    flat_e = top_i.reshape(t * k)
    flat_t = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(flat_e, stable=True)
    return order, flat_e[order], flat_t[order]


def _dispatch_dropless(p, cfg: ModelConfig, xf, top_w, top_i, impl):
    """Grouped dropless dispatch: every assignment to a held expert is
    honored; weights are renormalized over the row's own k experts only
    (cohort independent), or kept as routed (``norm_topk_prob=False``).
    A large cohort is dispatched in the fewest equal token chunks of at
    most DISPATCH_CHUNK tokens (``layers.even_chunks``), each rematerialised in the backward pass; a token's result does not
    depend on its chunk.  Returns (out (T, D), per held expert assignment
    counts (E_local,))."""
    t, d = xf.shape
    c = L.even_chunks(t, 1, DISPATCH_CHUNK)
    if c > 1:
        def chunk(args):
            return _dispatch_chunk(p, cfg, *args, impl)
        k = top_i.shape[-1]
        out, held = jax.lax.map(jax.checkpoint(chunk), (
            xf.reshape(c, t // c, d), top_w.reshape(c, t // c, k),
            top_i.reshape(c, t // c, k)))
        return out.reshape(t, d), held.sum(0)
    return _dispatch_chunk(p, cfg, xf, top_w, top_i, impl)


def _dispatch_chunk(p, cfg: ModelConfig, xf, top_w, top_i, impl):
    t, d = xf.shape
    e, k = cfg.local_experts, cfg.top_k
    if cfg.norm_topk_prob:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    share = e < cfg.n_experts
    if share:  # experts not held here sort after the held ones, as group e
        local = top_i - cfg.expert_offset
        top_i = jnp.where((local >= 0) & (local < e), local, e)
    order, se, st = _sort_by_expert(top_i, t, k)
    sw = top_w.reshape(t * k)[order].astype(jnp.float32)
    group_sizes = jnp.zeros((e,), jnp.int32).at[top_i.reshape(-1)].add(
        1, mode="drop")
    with jax.named_scope("moe.experts"):
        # at most T*K rows; the grouped GEMM skips what no held expert has
        ys = ops.grouped_ffn(xf[st], group_sizes, p["w_gate"], p["w_in"],
                             p["w_out"], act=cfg.act, impl=impl)  # f32
        if share:  # rows past the held groups are undefined in the kernel
            ys = jnp.where((se < e)[:, None], ys, 0.0)
        out = jnp.zeros((t, d), jnp.float32).at[st].add(ys * sw[:, None])
    return out.astype(xf.dtype), group_sizes


def capacity_route(cfg: ModelConfig, top_w, top_i, t: int):
    """Capacity-drop routing decisions for a T-token cohort.

    Returns (order, st, slot, keep, sw, c): sorted token ids, dispatch
    slots (``e*c`` = overflow/dropped), the sorted keep mask, and the
    combine weights renormalized over each row's *kept* experts (a row that
    loses an expert to the capacity limit redistributes its weight over the
    survivors instead of silently under-weighting them)."""
    e, k = cfg.n_experts, cfg.top_k
    c = capacity(t, cfg)
    order, se, st = _sort_by_expert(top_i, t, k)
    counts = jnp.zeros((e,), jnp.int32).at[top_i.reshape(-1)].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(t * k) - starts[se]
    keep = rank < c
    slot = jnp.where(keep, se * c + rank, e * c)  # overflow slot dropped
    keep_tk = jnp.zeros((t * k,), bool).at[order].set(keep).reshape(t, k)
    w_kept = top_w * keep_tk
    w = w_kept / jnp.maximum(w_kept.sum(-1, keepdims=True), 1e-9)
    sw = w.reshape(t * k)[order].astype(jnp.float32)
    return order, st, slot, keep, sw, c


def _dispatch_capacity(p, cfg: ModelConfig, xf, top_w, top_i):
    t, d = xf.shape
    e = cfg.n_experts
    _, st, slot, keep, sw, c = capacity_route(cfg, top_w, top_i, t)

    buf = jnp.zeros((e * c + 1, d), xf.dtype).at[slot].set(xf[st])
    xe = buf[:-1].reshape(e, c, d)

    # expert compute (EP shards the leading E axis)
    g = L.act_fn(cfg.act)(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"]))
    h = g * jnp.einsum("ecd,edf->ecf", xe, p["w_in"])
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_out"]).reshape(e * c, d)

    contrib = ye[jnp.minimum(slot, e * c - 1)].astype(jnp.float32) * (
        sw * keep.astype(jnp.float32))[:, None]
    out = jnp.zeros((t, d), jnp.float32).at[st].add(contrib)
    return out.astype(xf.dtype)


def moe_apply(p, cfg: ModelConfig, x, *, impl="reference", want_aux=True):
    """x: (B, S, D) -> (B, S, D) plus aux load-balancing loss and the
    routing counters ({"routed", "held", "held_max"}, module doc).

    ``want_aux=False`` (serving paths: prefill/decode) skips the aux-loss
    computation entirely — it is dead work outside the training forward —
    and returns a constant 0.0 in its place."""
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)

    with jax.named_scope("moe.route"):
        probs, top_w, top_i = _router(p, cfg, xf)
        aux_loss = (_aux_loss(probs, top_i, cfg.n_experts) if want_aux
                    else jnp.zeros((), jnp.float32))

    if cfg.moe_dispatch == "dropless":
        out, held = _dispatch_dropless(p, cfg, xf, top_w, top_i, impl)
    else:
        if cfg.local_experts != cfg.n_experts or not cfg.norm_topk_prob:
            raise NotImplementedError("capacity dispatch holds every expert "
                                      "and renormalises")
        out = _dispatch_capacity(p, cfg, xf, top_w, top_i)
        held = jnp.zeros((cfg.n_experts,), jnp.int32).at[
            top_i.reshape(-1)].add(1)
    out = out.reshape(b, s, d)

    if "dense" in p:
        out = out + L.mlp_apply(p["dense"], cfg, x)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            out = out + L.mlp_apply(p["shared"], cfg, x)
    return out, aux_loss, {"routed": jnp.int32(t * cfg.top_k),
                           "held": held.sum(), "held_max": held.max()}
