"""Reward model: value-head trunk scored at the last valid token."""

from __future__ import annotations

import jax.numpy as jnp

from repro.models import model as MDL


def score_sequences(params, cfg, tokens, mask, *, impl="reference"):
    """tokens: (B, S); mask: (B, S) — returns (scalar reward per sequence
    (B,), the forward's MoE routing counters ({} without MoE))."""
    h, _, counts = MDL.forward(params, cfg, {"tokens": tokens}, impl=impl,
                               remat=False)
    v = MDL.values_of(params, h)  # (B, S)
    idx = jnp.maximum(mask.sum(-1).astype(jnp.int32) - 1, 0)
    return jnp.take_along_axis(v, idx[:, None], axis=-1)[:, 0], counts
