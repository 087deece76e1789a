"""PPO for RLHF: per-token KL-shaped rewards, GAE, clipped surrogate +
clipped value loss, and the paper's *minibatched* PPO update (parameter
update per minibatch, NOT gradient accumulation — §2.1).

Shapes: B = #sequences, T = generated tokens per sequence.  All tensors are
aligned to the generated region; prompt tokens never enter the loss.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import model as MDL
from repro.optim import adamw


@dataclasses.dataclass(frozen=True)
class PPOHyperparameters:
    gamma: float = 1.0
    lam: float = 0.95
    clip_eps: float = 0.2
    value_clip: float = 0.2
    kl_coef: float = 0.1
    entropy_coef: float = 0.0
    n_minibatches: int = 8
    value_coef: float = 0.5


def shaped_rewards(hp: PPOHyperparameters, final_reward, logp, ref_logp, mask):
    """Token rewards: -kl_coef*(logp - ref_logp) with the sequence reward on
    the last valid token.  final_reward: (B,), rest (B, T)."""
    kl = (logp - ref_logp) * mask
    r = -hp.kl_coef * kl
    last = (mask.cumsum(-1) == mask.sum(-1, keepdims=True)) & (mask > 0)
    return r + final_reward[:, None] * last.astype(r.dtype)


def gae(hp: PPOHyperparameters, rewards, values, mask):
    """values: (B, T+1) (bootstrap column at the end).  Returns (adv, ret)."""
    b, t = rewards.shape

    def step(carry, inp):
        r, v, v_next, m = inp
        delta = r + hp.gamma * v_next * m - v
        carry = delta + hp.gamma * hp.lam * m * carry
        return carry, carry

    seq = (rewards.T, values[:, :-1].T, values[:, 1:].T, mask.T)
    _, adv_rev = jax.lax.scan(step, jnp.zeros((b,), rewards.dtype), seq,
                              reverse=True)
    adv = adv_rev.T * mask
    ret = adv + values[:, :-1] * mask
    # advantage whitening over valid tokens
    n = jnp.maximum(mask.sum(), 1.0)
    mean = (adv * mask).sum() / n
    var = (jnp.square(adv - mean) * mask).sum() / n
    adv = (adv - mean) * jax.lax.rsqrt(var + 1e-8) * mask
    return adv, ret


def advantages(hp: PPOHyperparameters, final_reward, logp, ref_logp, values,
               mask):
    """The padded path's whole advantage estimate, :func:`shaped_rewards`
    then :func:`gae`, as one function to ``jit`` with ``hp`` closed over.
    Returns (adv, ret)."""
    return gae(hp, shaped_rewards(hp, final_reward, logp, ref_logp, mask),
               values, mask)


def actor_loss_fn(hp: PPOHyperparameters, new_logp, old_logp, adv, mask):
    ratio = jnp.exp(jnp.clip(new_logp - old_logp, -20.0, 20.0))
    unclipped = ratio * adv
    clipped = jnp.clip(ratio, 1 - hp.clip_eps, 1 + hp.clip_eps) * adv
    per_tok = -jnp.minimum(unclipped, clipped) * mask
    n = jnp.maximum(mask.sum(), 1.0)
    frac_clipped = ((unclipped > clipped) * mask).sum() / n
    return per_tok.sum() / n, {"clip_frac": frac_clipped,
                               "ratio_mean": (ratio * mask).sum() / n}


def critic_loss_fn(hp: PPOHyperparameters, new_values, old_values, returns,
                   mask):
    clipped = old_values + jnp.clip(new_values - old_values, -hp.value_clip,
                                    hp.value_clip)
    l1 = jnp.square(new_values - returns)
    l2 = jnp.square(clipped - returns)
    n = jnp.maximum(mask.sum(), 1.0)
    return 0.5 * (jnp.maximum(l1, l2) * mask).sum() / n


# ------------------------------------------------------------- model glue

def sequence_logprobs(params, cfg, tokens, gen_start: int, *,
                      impl="reference", remat=True):
    """Log-probs of tokens[t] under the model for the generated region.
    tokens: (B, S).  Returns ((B, S - gen_start), the forward's MoE routing
    counters ({} without MoE, ``model.forward``))."""
    h, _, counts = MDL.forward(params, cfg, {"tokens": tokens}, impl=impl,
                               remat=remat)
    logits = MDL.logits_of(params, cfg, h)  # (B, S, V)
    lp = jax.nn.log_softmax(logits[:, gen_start - 1:-1], axis=-1)
    tgt = tokens[:, gen_start:]
    return jnp.take_along_axis(lp, tgt[..., None], axis=-1)[..., 0], counts


def sequence_values(params, cfg, tokens, gen_start: int, *, impl="reference",
                    remat=True):
    """Critic values for positions gen_start-1 .. S-1 => (B, T+1) with
    bootstrap column, and the forward's routing counters."""
    h, _, counts = MDL.forward(params, cfg, {"tokens": tokens}, impl=impl,
                               remat=remat)
    return MDL.values_of(params, h)[:, gen_start - 1:], counts


def _reduce_stats(stats):
    """Per-minibatch stats to the step's: means, except the MoE routing
    counters (``moe``), which are summed and left out where empty."""
    moe = stats.pop("moe")
    stats = jax.tree.map(jnp.mean, stats)
    return {**stats, "moe": jax.tree.map(lambda x: x.sum(0), moe)} \
        if moe else stats


# ------------------------------------------------------------ train steps

def make_actor_train_step(cfg, hp: PPOHyperparameters, opt: adamw.AdamWConfig,
                          gen_start: int, *, impl="reference"):
    """Returns jit-able f(params, opt_state, batch) -> (params, opt_state,
    stats).  Runs hp.n_minibatches sequential PPO updates (param update per
    minibatch, matching the paper's workload definition).  MoE models'
    stats add ``moe``, the forwards' routing counters summed over the
    minibatches."""

    def minibatch_update(carry, mb):
        params, opt_state = carry

        def loss(p, mb):
            new_logp, counts = sequence_logprobs(p, cfg, mb["tokens"],
                                                 gen_start, impl=impl)
            l, stats = actor_loss_fn(hp, new_logp, mb["logp"], mb["adv"],
                                     mb["mask"])
            return l, {**stats, "moe": counts}

        (l, stats), grads = jax.value_and_grad(loss, has_aux=True)(params, mb)
        params, opt_state, ostats = adamw.update(opt, params, opt_state, grads)
        return (params, opt_state), {"loss": l, **stats, **ostats}

    def step(params, opt_state, batch):
        nmb = hp.n_minibatches
        mbs = jax.tree.map(
            lambda x: x.reshape(nmb, x.shape[0] // nmb, *x.shape[1:]), batch)
        (params, opt_state), stats = jax.lax.scan(
            minibatch_update, (params, opt_state), mbs)
        return params, opt_state, _reduce_stats(stats)

    return step


# -------------------------------------------------- packed (cu_seqlens) path
#
# The packed layout flattens the cohort to one (T,) token axis with
# ``cu_seqlens`` segment offsets (data/packing.py).  Alignment convention
# for every per-token array below: index j is the *target* token, i.e.
# new_logp[j] = log_softmax(logits[j-1])[tokens[j]], v_pred[j] =
# values[j-1], v_next[j] = values[j].  With right-padded inputs and one
# post-EOS bootstrap token kept per sequence, the packed losses/advantages
# match the padded ones exactly on valid tokens (tests/test_packed.py);
# phantom tokens beyond cu_seqlens[-1] carry mask 0 everywhere.


def packed_segment_ids(cu_seqlens, total: int):
    """(T,) int32 sequence id per token; phantoms get id B."""
    return jnp.searchsorted(cu_seqlens[1:], jnp.arange(total),
                            side="right").astype(jnp.int32)


def packed_last_valid(mask, cu_seqlens):
    """0/1 flag of each sequence's last mask>0 token (packed analogue of
    ``shaped_rewards``' ``last``).  mask: (T,)."""
    t = mask.shape[0]
    b = cu_seqlens.shape[0] - 1
    seg = packed_segment_ids(cu_seqlens, t)
    segc = jnp.minimum(seg, b - 1)
    cm = jnp.cumsum(mask)
    excl = cm - mask
    start = excl[cu_seqlens[:-1]]            # (B,) offset before each seq
    total_m = cm[cu_seqlens[1:] - 1] - start  # (B,) mask sum within seq
    within = cm - start[segc]
    return ((within == total_m[segc]) & (mask > 0)
            & (seg < b)).astype(mask.dtype)


def shaped_rewards_packed(hp: PPOHyperparameters, final_reward, logp,
                          ref_logp, mask, cu_seqlens):
    """Packed :func:`shaped_rewards`: final_reward (B,), rest (T,)."""
    kl = (logp - ref_logp) * mask
    r = -hp.kl_coef * kl
    b = cu_seqlens.shape[0] - 1
    seg = jnp.minimum(packed_segment_ids(cu_seqlens, mask.shape[0]), b - 1)
    last = packed_last_valid(mask, cu_seqlens)
    return r + final_reward[seg] * last


def gae_packed(hp: PPOHyperparameters, rewards, v_pred, v_next, mask,
               cu_seqlens):
    """Packed :func:`gae`: one reverse scan over the (T,) token axis with
    the carry reset at sequence ends (``cu_seqlens[1:] - 1``), so the
    recurrence never crosses a segment boundary.  All args (T,); returns
    (adv, ret) both (T,)."""
    t = rewards.shape[0]
    is_end = jnp.zeros((t,), rewards.dtype).at[cu_seqlens[1:] - 1].set(1.0)

    def step(carry, inp):
        r, vp, vn, m, e = inp
        carry = jnp.where(e > 0, 0.0, carry)
        delta = r + hp.gamma * vn * m - vp
        carry = delta + hp.gamma * hp.lam * m * carry
        return carry, carry

    _, adv_rev = jax.lax.scan(step, jnp.zeros((), rewards.dtype),
                              (rewards, v_pred, v_next, mask, is_end),
                              reverse=True)
    adv = adv_rev * mask
    ret = adv + v_pred * mask
    n = jnp.maximum(mask.sum(), 1.0)
    mean = (adv * mask).sum() / n
    var = (jnp.square(adv - mean) * mask).sum() / n
    adv = (adv - mean) * jax.lax.rsqrt(var + 1e-8) * mask
    return adv, ret


def packed_sequence_logprobs(params, cfg, batch, *, impl="reference",
                             remat=True, max_seqlen=None):
    """Target-aligned log-probs over a packed cohort: out[j] =
    log_softmax(logits[j-1])[tokens[j]] (out[0] = 0; the first packed
    token is always a prompt token with mask 0).  Returns (T,)."""
    h, _, _ = MDL.forward(params, cfg, batch, impl=impl, remat=remat,
                          max_seqlen=max_seqlen)
    logits = MDL.logits_of(params, cfg, h)[0]  # (T, V)
    lp = jax.nn.log_softmax(logits[:-1], axis=-1)
    tgt = batch["tokens"][1:]
    out = jnp.take_along_axis(lp, tgt[:, None], axis=-1)[:, 0]
    return jnp.concatenate([jnp.zeros((1,), out.dtype), out])


def packed_sequence_values(params, cfg, batch, *, impl="reference",
                           remat=True, max_seqlen=None):
    """Critic values per packed position => (T,).  The target-aligned
    prediction for token j is values[j-1] (shift with
    :func:`packed_shift_right`)."""
    h, _, _ = MDL.forward(params, cfg, batch, impl=impl, remat=remat,
                          max_seqlen=max_seqlen)
    return MDL.values_of(params, h)[0]


def packed_shift_right(x):
    """v_pred alignment: out[j] = x[j-1], out[0] = 0."""
    return jnp.concatenate([jnp.zeros((1,), x.dtype), x[:-1]])


def make_packed_actor_train_step(cfg, hp: PPOHyperparameters,
                                 opt: adamw.AdamWConfig, *,
                                 impl="reference", max_seqlen=None):
    """Packed analogue of :func:`make_actor_train_step`.  ``batch`` holds
    (nmb, Tmb)-stacked arrays from ``packing.pack_minibatches``: "tokens",
    "positions", "logp", "adv", "mask" plus (nmb, B/nmb + 1) "cu_seqlens"."""

    def minibatch_update(carry, mb):
        params, opt_state = carry

        def loss(p, mb):
            new_logp = packed_sequence_logprobs(
                p, cfg, {"tokens": mb["tokens"],
                         "cu_seqlens": mb["cu_seqlens"],
                         "positions": mb["positions"]},
                impl=impl, max_seqlen=max_seqlen)
            return actor_loss_fn(hp, new_logp, mb["logp"], mb["adv"],
                                 mb["mask"])

        (l, stats), grads = jax.value_and_grad(loss, has_aux=True)(params, mb)
        params, opt_state, ostats = adamw.update(opt, params, opt_state, grads)
        return (params, opt_state), {"loss": l, **stats, **ostats}

    def step(params, opt_state, batch):
        (params, opt_state), stats = jax.lax.scan(
            minibatch_update, (params, opt_state), batch)
        return params, opt_state, jax.tree.map(jnp.mean, stats)

    return step


def make_packed_critic_train_step(cfg, hp: PPOHyperparameters,
                                  opt: adamw.AdamWConfig, *,
                                  impl="reference", max_seqlen=None):
    """Packed critic step; ``batch`` as the actor's but with "values"
    (old target-aligned predictions) and "ret" instead of logp/adv."""

    def minibatch_update(carry, mb):
        params, opt_state = carry

        def loss(p, mb):
            v = packed_sequence_values(
                p, cfg, {"tokens": mb["tokens"],
                         "cu_seqlens": mb["cu_seqlens"],
                         "positions": mb["positions"]},
                impl=impl, max_seqlen=max_seqlen)
            return critic_loss_fn(hp, packed_shift_right(v), mb["values"],
                                  mb["ret"], mb["mask"]), {}

        (l, _), grads = jax.value_and_grad(loss, has_aux=True)(params, mb)
        params, opt_state, ostats = adamw.update(opt, params, opt_state, grads)
        return (params, opt_state), {"loss": l, **ostats}

    def step(params, opt_state, batch):
        (params, opt_state), stats = jax.lax.scan(
            minibatch_update, (params, opt_state), batch)
        return params, opt_state, jax.tree.map(jnp.mean, stats)

    return step


def make_critic_train_step(cfg, hp: PPOHyperparameters, opt: adamw.AdamWConfig,
                           gen_start: int, *, impl="reference"):
    def minibatch_update(carry, mb):
        params, opt_state = carry

        def loss(p, mb):
            v, counts = sequence_values(p, cfg, mb["tokens"], gen_start,
                                        impl=impl)
            return critic_loss_fn(hp, v[:, :-1], mb["values"], mb["ret"],
                                  mb["mask"]), {"moe": counts}

        (l, aux), grads = jax.value_and_grad(loss, has_aux=True)(params, mb)
        params, opt_state, ostats = adamw.update(opt, params, opt_state, grads)
        return (params, opt_state), {"loss": l, **aux, **ostats}

    def step(params, opt_state, batch):
        nmb = hp.n_minibatches
        mbs = jax.tree.map(
            lambda x: x.reshape(nmb, x.shape[0] // nmb, *x.shape[1:]), batch)
        (params, opt_state), stats = jax.lax.scan(
            minibatch_update, (params, opt_state), mbs)
        return params, opt_state, _reduce_stats(stats)

    return step
