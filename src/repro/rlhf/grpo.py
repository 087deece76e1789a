"""GRPO (paper §8.3): grouped generation, group-relative advantages, no
critic.  The workload multiplies the generation batch by group_size, making
PPO-style training more compute-bound (the paper's Fig. 16 observation)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.optim import adamw
from repro.rlhf.ppo import actor_loss_fn, sequence_logprobs


@dataclasses.dataclass(frozen=True)
class GRPOHyperparameters:
    group_size: int = 8
    clip_eps: float = 0.2
    kl_coef: float = 0.04
    n_minibatches: int = 1


def group_advantages(rewards, group_size: int):
    """rewards: (B*G,) -> whitened within each group of G."""
    r = rewards.reshape(-1, group_size)
    mean = r.mean(-1, keepdims=True)
    std = r.std(-1, keepdims=True) + 1e-6
    return ((r - mean) / std).reshape(-1)


def make_grpo_train_step(cfg, hp: GRPOHyperparameters, opt: adamw.AdamWConfig,
                         gen_start: int, *, impl="reference"):
    """batch: {tokens (B*G, S), logp (B*G, T), ref_logp, mask, rewards (B*G,)}."""

    class _HP:  # adapt to actor_loss_fn's interface
        clip_eps = hp.clip_eps

    def step(params, opt_state, batch):
        adv_seq = group_advantages(batch["rewards"], hp.group_size)
        adv = adv_seq[:, None] * batch["mask"]

        def loss(p):
            new_logp, _ = sequence_logprobs(p, cfg, batch["tokens"],
                                            gen_start, impl=impl)
            l, stats = actor_loss_fn(_HP, new_logp, batch["logp"], adv,
                                     batch["mask"])
            # GRPO's explicit KL regularizer (k3 estimator)
            lr = batch["ref_logp"] - new_logp
            kl = (jnp.exp(lr) - lr - 1.0) * batch["mask"]
            n = jnp.maximum(batch["mask"].sum(), 1.0)
            return l + hp.kl_coef * kl.sum() / n, stats

        (l, stats), grads = jax.value_and_grad(loss, has_aux=True)(params)
        params, opt_state, ostats = adamw.update(opt, params, opt_state, grads)
        return params, opt_state, {"loss": l, **stats, **ostats}

    return step
