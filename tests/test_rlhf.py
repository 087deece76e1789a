"""RLHF algorithm math: GAE vs. a naive python reference, PPO clipping,
DPO/GRPO properties."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.rlhf import dpo as DPO
from repro.rlhf import grpo as GRPO
from repro.rlhf import ppo as PPO

HP = PPO.PPOHyperparameters(gamma=0.97, lam=0.9, kl_coef=0.05)


def naive_gae(hp, rewards, values, mask):
    b, t = rewards.shape
    adv = np.zeros((b, t))
    for i in range(b):
        last = 0.0
        for j in reversed(range(t)):
            delta = rewards[i, j] + hp.gamma * values[i, j + 1] * mask[i, j] \
                - values[i, j]
            last = delta + hp.gamma * hp.lam * mask[i, j] * last
            adv[i, j] = last
    return adv * mask


def whiten(raw, mask):
    """The advantage whitening over valid tokens that ``PPO.gae`` applies."""
    n = max(mask.sum(), 1.0)
    mean = (raw * mask).sum() / n
    var = (((raw - mean) ** 2) * mask).sum() / n
    return (raw - mean) / np.sqrt(var + 1e-8) * mask


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 4), st.integers(2, 12), st.integers(0, 10**6))
def test_gae_matches_naive(b, t, seed):
    rng = np.random.default_rng(seed)
    rewards = rng.normal(size=(b, t)).astype(np.float32)
    values = rng.normal(size=(b, t + 1)).astype(np.float32)
    lens = rng.integers(1, t + 1, b)
    mask = (np.arange(t)[None] < lens[:, None]).astype(np.float32)
    adv, ret = PPO.gae(HP, jnp.asarray(rewards), jnp.asarray(values),
                       jnp.asarray(mask))
    raw = naive_gae(HP, rewards, values, mask)
    np.testing.assert_allclose(np.asarray(adv), whiten(raw, mask), atol=2e-3)
    np.testing.assert_allclose(np.asarray(ret), raw + values[:, :-1] * mask,
                               atol=2e-3)


@pytest.mark.parametrize("b,t,lens", [
    (1, 2, [2]),                  # one sequence, no EOS
    (3, 9, [9, 4, 1]),            # EOS early, one after the first token
    (4, 16, [16, 16, 16, 16]),    # every mask full
    (8, 64, [64, 7, 33, 2, 64, 50, 1, 63]),
])
def test_jitted_advantages_match_eager_and_naive(b, t, lens):
    rng = np.random.default_rng(b * 1000 + t)
    final = rng.normal(size=(b,)).astype(np.float32)
    logp = -rng.exponential(size=(b, t)).astype(np.float32)
    ref_logp = -rng.exponential(size=(b, t)).astype(np.float32)
    values = rng.normal(size=(b, t + 1)).astype(np.float32)
    mask = (np.arange(t)[None] < np.asarray(lens)[:, None]).astype(
        np.float32)
    args = tuple(map(jnp.asarray, (final, logp, ref_logp, values, mask)))
    adv, ret = jax.jit(functools.partial(PPO.advantages, HP))(*args)
    shaped = PPO.shaped_rewards(HP, *args[:3], args[4])
    e_adv, e_ret = PPO.gae(HP, shaped, args[3], args[4])
    np.testing.assert_allclose(np.asarray(adv), np.asarray(e_adv),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ret), np.asarray(e_ret),
                               rtol=1e-5, atol=1e-6)
    raw = naive_gae(HP, np.asarray(shaped, np.float64), values, mask)
    np.testing.assert_allclose(np.asarray(adv), whiten(raw, mask), atol=1e-4)
    np.testing.assert_allclose(np.asarray(ret), raw + values[:, :-1] * mask,
                               atol=1e-4)


def test_shaped_rewards_places_final_reward_at_last_token():
    hp = PPO.PPOHyperparameters(kl_coef=0.0)
    final = jnp.array([2.0, -1.0])
    logp = jnp.zeros((2, 4))
    mask = jnp.array([[1, 1, 1, 0], [1, 1, 1, 1]], jnp.float32)
    r = PPO.shaped_rewards(hp, final, logp, logp, mask)
    np.testing.assert_allclose(np.asarray(r[0]), [0, 0, 2.0, 0])
    np.testing.assert_allclose(np.asarray(r[1]), [0, 0, 0, -1.0])


def test_ppo_clip_blocks_large_ratios():
    hp = PPO.PPOHyperparameters(clip_eps=0.2)
    mask = jnp.ones((1, 3))
    adv = jnp.ones((1, 3))
    old = jnp.zeros((1, 3))
    # within the trust region the loss improves with logp; far outside it
    # the clipped objective is flat => equal losses
    l1, _ = PPO.actor_loss_fn(hp, jnp.full((1, 3), 1.0), old, adv, mask)
    l2, _ = PPO.actor_loss_fn(hp, jnp.full((1, 3), 2.0), old, adv, mask)
    assert np.isclose(float(l1), float(l2))  # both clipped at 1+eps


def test_critic_value_clip():
    hp = PPO.PPOHyperparameters(value_clip=0.1)
    mask = jnp.ones((1, 2))
    old = jnp.zeros((1, 2))
    ret = jnp.ones((1, 2))
    small = PPO.critic_loss_fn(hp, jnp.full((1, 2), 0.05), old, ret, mask)
    big = PPO.critic_loss_fn(hp, jnp.full((1, 2), 2.0), old, ret, mask)
    # moving beyond the clip radius cannot reduce the loss below the clipped value
    assert float(big) >= float(small)


def test_dpo_loss_prefers_chosen():
    hp = DPO.DPOHyperparameters(beta=0.5)
    good = jnp.array([2.0, 1.0])
    bad = jnp.array([-1.0, -2.0])
    ref = jnp.zeros(2)
    l_right, stats = DPO.dpo_loss(hp, good, bad, ref, ref)
    l_wrong, _ = DPO.dpo_loss(hp, bad, good, ref, ref)
    assert float(l_right) < float(l_wrong)
    assert float(stats["dpo_acc"]) == 1.0


def test_grpo_group_advantages_zero_mean_unit_std():
    rng = np.random.default_rng(0)
    r = jnp.asarray(rng.normal(size=(32,)).astype(np.float32))
    adv = GRPO.group_advantages(r, group_size=8)
    g = np.asarray(adv).reshape(4, 8)
    np.testing.assert_allclose(g.mean(-1), 0.0, atol=1e-5)
    np.testing.assert_allclose(g.std(-1), 1.0, atol=2e-2)
