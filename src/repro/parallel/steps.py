"""Jit-able train / prefill / decode steps with explicit in/out shardings.

These are the functions the multi-pod dry-run lowers for every
(architecture x shape x mesh) cell, and the building blocks the ReaL runtime
dispatches per function call.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import model as MDL
from repro.models import transformer as T
from repro.optim import adamw
from repro.parallel import sharding as SH


def make_train_step(cfg: ModelConfig, opt_cfg: adamw.AdamWConfig, *,
                    impl="reference", remat=True, n_micro: int = 1):
    """(params, opt_state, batch) -> (params, opt_state, metrics)."""

    def loss_fn(params, batch):
        return MDL.lm_loss(params, cfg, batch, impl=impl, remat=remat)

    def step(params, opt_state, batch):
        from repro.optim.grad import accumulate_grads
        loss, grads, aux = accumulate_grads(loss_fn, params, batch, n_micro)
        params, opt_state, stats = adamw.update(opt_cfg, params, opt_state,
                                                grads)
        return params, opt_state, {"loss": loss, **aux, **stats}

    return step


def make_prefill_step(cfg: ModelConfig, *, impl="reference",
                      extra_len: int = 0):
    """(params, batch) -> (next_token_logits, caches)."""

    def step(params, batch):
        max_len = batch["tokens"].shape[1] + max(extra_len, 1)
        last_h, caches, _ = MDL.prefill(params, cfg, batch, max_len,
                                        impl=impl)
        logits = MDL.logits_of(params, cfg, last_h[:, None])[:, 0]
        return logits, caches

    return step


def make_decode_step(cfg: ModelConfig, *, impl="reference"):
    """(params, token (B,), caches, t) -> (logits, caches).  ``serve_step``
    for the decode_* / long_* shape cells: one new token against a cache."""

    def step(params, token, caches, t):
        logits, caches, _ = MDL.decode_step(params, cfg, token, caches, t,
                                            impl=impl)
        return logits, caches

    return step


# ----------------------------------------------------------- dry-run wiring

def shardings_for_cell(cfg: ModelConfig, mesh, *, multi_pod: bool):
    rules = SH.ShardingRules(
        tp_axis="model", fsdp_axis="data", dp_axes=("data",),
        pod_axis="pod" if multi_pod else None)
    return rules


def cache_specs(cfg: ModelConfig, batch: int, max_len: int):
    """ShapeDtypeStructs of the decode caches (dry-run input stand-ins)."""
    dt = jnp.dtype(cfg.dtype)
    cross = cfg.family == "encdec"
    shapes = jax.eval_shape(
        lambda: T.cache_init(cfg, batch, max_len, dt, cross=cross,
                             enc_len=cfg.prefix_len if cross else None))
    return shapes


def cache_partition_specs(cache_shapes, rules: SH.ShardingRules):
    """KV caches: batch over (pod+)data, head/state dim over model."""
    bax = rules.batch_axes
    b = bax if len(bax) > 1 else (bax[0] if bax else None)

    def spec(x):
        # leading dim is the scan-stack; dim1 is batch
        if x.ndim >= 4:  # (n, B, S, H, D) kv or (n, B, H, P, N) ssm
            parts = [None, b] + [None] * (x.ndim - 3) + [rules.tp_axis]
            # shard the last dim over tp only if divisible
            if x.shape[-1] % 16 != 0:
                parts[-1] = None
            return P(*parts)
        if x.ndim >= 2:
            return P(None, b, *([None] * (x.ndim - 2)))
        return P(None)

    return jax.tree.map(spec, cache_shapes)
