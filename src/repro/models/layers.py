"""Foundational layers: RMSNorm, RoPE, embeddings, gated MLP, init helpers.

All models are pure-functional: ``init_*`` builds a (nested-dict) param tree,
``*_apply`` consumes it.  Compute follows a bf16-with-fp32-reductions policy;
norms and softmax run in fp32 regardless of the param/activation dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.ref import ACTS


def truncated_normal(key, shape, dtype, scale):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


def dense_init(key, d_in, d_out, dtype, with_bias=False):
    scale = d_in ** -0.5
    p = {"w": truncated_normal(key, (d_in, d_out), dtype, scale)}
    if with_bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense_apply(p, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def rmsnorm_init(dim, dtype):
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm_apply(p, x, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


# ----------------------------------------------------------------- RoPE

def rope_apply(x, positions, theta: float):
    """x: (B, S, H, D) with even D; positions: (B, S) or (S,)."""
    d = x.shape[-1]
    assert d % 2 == 0
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)  # (D/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- MLP

def act_fn(name: str):
    return ACTS[name]  # single registry shared with the kernel tiers


def mlp_init(key, cfg: ModelConfig, d_ff: int | None = None):
    d_ff = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.dtype)
    return {
        "w_gate": dense_init(k1, cfg.d_model, d_ff, dt),
        "w_in": dense_init(k2, cfg.d_model, d_ff, dt),
        "w_out": dense_init(k3, d_ff, cfg.d_model, dt),
    }


def mlp_apply(p, cfg: ModelConfig, x):
    g = act_fn(cfg.act)(dense_apply(p["w_gate"], x))
    h = g * dense_apply(p["w_in"], x)
    return dense_apply(p["w_out"], h)


# ----------------------------------------------------------------- Embedding

def embed_init(key, cfg: ModelConfig):
    """A tied table is also the LM head, so it is drawn at the head's
    d_model**-0.5: at std 1 the logits would have std ~sqrt(d_model) and
    every sampled token a logprob of ~0."""
    dt = jnp.dtype(cfg.dtype)
    scale = cfg.d_model ** -0.5 if cfg.tie_embeddings else 1.0
    p = {"table": truncated_normal(key, (cfg.vocab_size, cfg.d_model), dt,
                                   scale)}
    return p


def embed_apply(p, tokens):
    return p["table"][tokens]


def unembed_apply(p_head, p_embed, x, tie: bool):
    """Returns logits in fp32."""
    if tie:
        w = p_embed["table"]
    else:
        w = p_head["w"]
        return (x @ w).astype(jnp.float32)
    return jnp.einsum("bsd,vd->bsv", x, w).astype(jnp.float32)


def cross_entropy(logits, labels, mask):
    """logits: (B,S,V) fp32; labels: (B,S) int32; mask: (B,S) {0,1}.
    Returns (mean_loss, token_count)."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    return nll.sum() / denom, denom


def chunked_lm_head_loss(head_fn, hidden, labels, mask, chunk: int = 0):
    """Sequence-chunked LM head + cross-entropy with per-chunk remat.

    Never materializes the full (B, S, V) logits: each chunk's logits are
    recomputed in the backward pass (jax.checkpoint), bounding the head's
    working set to (B, chunk, V).  Exact.  ``head_fn(h_chunk) -> logits``.
    """
    b, s, _ = hidden.shape
    if chunk == 0:
        chunk = 512 if s >= 4096 else 0
    if not chunk or s <= chunk or s % chunk != 0:
        logits = head_fn(hidden)
        return cross_entropy(logits, labels, mask)

    @jax.checkpoint
    def chunk_nll(h_c, y_c, m_c):
        logits = head_fn(h_c)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y_c[..., None], axis=-1)[..., 0]
        return ((logz - gold) * m_c).sum()

    total = 0.0
    for i in range(s // chunk):
        sl = slice(i * chunk, (i + 1) * chunk)
        total = total + chunk_nll(hidden[:, sl], labels[:, sl], mask[:, sl])
    denom = jnp.maximum(mask.sum(), 1.0)
    return total / denom, denom
