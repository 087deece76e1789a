"""ReMax (paper §8.3): REINFORCE with a greedy-rollout baseline.  Its two
generation calls are independent — the dfg lets REAL run them concurrently,
which is why ReMax shows the largest plan-search gain in Fig. 16."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.optim import adamw
from repro.rlhf.ppo import sequence_logprobs


@dataclasses.dataclass(frozen=True)
class ReMaxHyperparameters:
    kl_coef: float = 0.05


def make_remax_train_step(cfg, hp: ReMaxHyperparameters,
                          opt: adamw.AdamWConfig, gen_start: int, *,
                          impl="reference"):
    """batch: {tokens (B,S), mask (B,T...), rewards (B,), rewards_baseline (B,),
    ref_logp (B,T)}."""

    def step(params, opt_state, batch):
        adv = (batch["rewards"] - batch["rewards_baseline"])[:, None]

        def loss(p):
            new_logp, _ = sequence_logprobs(p, cfg, batch["tokens"],
                                            gen_start, impl=impl)
            kl = (new_logp - batch["ref_logp"]) * batch["mask"]
            pg = -(adv * new_logp * batch["mask"])
            n = jnp.maximum(batch["mask"].sum(), 1.0)
            return (pg.sum() + hp.kl_coef * kl.sum()) / n, {}

        (l, _), grads = jax.value_and_grad(loss, has_aux=True)(params)
        params, opt_state, ostats = adamw.update(opt, params, opt_state, grads)
        return params, opt_state, {"loss": l, **ostats}

    return step
