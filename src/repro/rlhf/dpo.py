"""Direct Preference Optimization (paper §8.3): two function calls —
reference inference over (chosen, rejected) pairs, then policy training."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.optim import adamw
from repro.rlhf.ppo import sequence_logprobs


@dataclasses.dataclass(frozen=True)
class DPOHyperparameters:
    beta: float = 0.1


def dpo_loss(hp: DPOHyperparameters, pol_chosen, pol_rejected, ref_chosen,
             ref_rejected):
    """Sequence-level summed logprobs, (B,).  Returns (loss, stats)."""
    logits = hp.beta * ((pol_chosen - ref_chosen)
                        - (pol_rejected - ref_rejected))
    loss = -jax.nn.log_sigmoid(logits).mean()
    acc = (logits > 0).mean()
    return loss, {"dpo_acc": acc, "margin": logits.mean()}


def seq_logp_sum(params, cfg, tokens, mask, gen_start, *, impl="reference"):
    lp, _ = sequence_logprobs(params, cfg, tokens, gen_start, impl=impl)
    return (lp * mask[:, gen_start:]).sum(-1)


def make_dpo_train_step(cfg, hp: DPOHyperparameters, opt: adamw.AdamWConfig,
                        gen_start: int, *, impl="reference"):
    """batch: {chosen, rejected: (B,S) int32; chosen_mask, rejected_mask;
    ref_chosen_logp, ref_rejected_logp: (B,)}."""

    def step(params, opt_state, batch):
        def loss(p):
            pc = seq_logp_sum(p, cfg, batch["chosen"], batch["chosen_mask"],
                              gen_start, impl=impl)
            pr = seq_logp_sum(p, cfg, batch["rejected"],
                              batch["rejected_mask"], gen_start, impl=impl)
            return dpo_loss(hp, pc, pr, batch["ref_chosen_logp"],
                            batch["ref_rejected_logp"])

        (l, stats), grads = jax.value_and_grad(loss, has_aux=True)(params)
        params, opt_state, ostats = adamw.update(opt, params, opt_state, grads)
        return params, opt_state, {"loss": l, **stats, **ostats}

    return step
