"""Packed (cu_seqlens) training parity harness.

The correctness contract of the packed layout (ISSUE 8 / ROADMAP item 3):
the packed step matches the padded step **to fp32 tolerance on identical
logical inputs**.  This file enforces it at three levels: pure pack/unpack
round-trips (hypothesis-fuzzed), varlen-attention kernel tier parity plus
a bit-identical cross-sequence-leakage check, and full PPO loss/grad
parity across ragged length mixes (len-1 sequences, all-equal lengths, a
single max-length sequence).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import ARCHS
from repro.data import packing
from repro.kernels import ops, ref
from repro.models import model as MDL
from repro.rlhf import ppo as PPO

HP = PPO.PPOHyperparameters(gamma=0.97, lam=0.9, kl_coef=0.05)

# the ragged mixes the parity contract names explicitly: a long-tail mix,
# len-1 sequences, all lengths equal, and one single max-length sequence
LENGTH_MIXES = [
    pytest.param([3, 12, 1, 7], id="long-tail"),
    pytest.param([1, 1, 1, 1], id="all-len-1"),
    pytest.param([6, 6, 6, 6], id="all-equal"),
    pytest.param([12], id="single-max"),
]


# ------------------------------------------------------------ pack/unpack

@pytest.mark.parametrize("lens", LENGTH_MIXES)
def test_pack_unpack_roundtrip(lens):
    rng = np.random.default_rng(0)
    s = max(lens)
    x = jnp.asarray(rng.standard_normal((len(lens), s, 3)), jnp.float32)
    xp = packing.pack(x, lens)
    assert xp.shape[0] == sum(lens)
    back = packing.unpack(xp, lens, s)
    mask = (np.arange(s)[None] < np.asarray(lens)[:, None])
    np.testing.assert_array_equal(np.asarray(back)[mask],
                                  np.asarray(x)[mask])
    np.testing.assert_array_equal(np.asarray(back)[~mask], 0.0)


def test_packed_batch_container():
    toks = jnp.arange(12, dtype=jnp.int32).reshape(3, 4)
    pb = packing.pack_batch(toks, [2, 4, 1])
    assert pb.total_tokens == 7 and pb.n_seqs == 3 and pb.max_len == 4
    np.testing.assert_array_equal(np.asarray(pb.cu_seqlens), [0, 2, 6, 7])
    np.testing.assert_array_equal(np.asarray(pb.positions),
                                  [0, 1, 0, 1, 2, 3, 0])
    np.testing.assert_array_equal(np.asarray(pb.tokens),
                                  [0, 1, 4, 5, 6, 7, 8])
    # PackedBatch is a pytree: jit boundaries keep max_len static
    leaves, treedef = jax.tree.flatten(pb)
    pb2 = jax.tree.unflatten(treedef, leaves)
    assert pb2.max_len == 4
    # phantom padding extends tokens but not cu_seqlens
    padded = packing.pad_to(pb, 16)
    assert padded.tokens.shape[0] == 16
    np.testing.assert_array_equal(np.asarray(padded.cu_seqlens),
                                  np.asarray(pb.cu_seqlens))


def test_synth_packed_batch_matches_padded():
    from repro.data.synth import PromptDataset
    ds = PromptDataset(64, 10, 4, seed=3, min_len=2)
    padded = ds.batch_at(5)
    pb = ds.packed_batch_at(5)
    lens = np.asarray(padded["prompt_mask"].sum(-1), np.int64)
    np.testing.assert_array_equal(
        np.asarray(pb.cu_seqlens), packing.cu_seqlens_of(lens))
    np.testing.assert_array_equal(
        np.asarray(pb.tokens), np.asarray(packing.pack(padded["tokens"],
                                                       lens)))


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_pack_roundtrip_and_masked_loss_property(data):
    """Fuzz random cu_seqlens partitions: pack/unpack inverse and
    mask-weighted loss equality between layouts."""
    b = data.draw(st.integers(1, 6))
    s = data.draw(st.integers(1, 16))
    lens = np.asarray([data.draw(st.integers(1, s)) for _ in range(b)])
    seed = data.draw(st.integers(0, 10**6))
    rng = np.random.default_rng(seed)
    cu = packing.cu_seqlens_of(lens)
    assert cu[-1] == lens.sum() and (np.diff(cu) == lens).all()

    x = jnp.asarray(rng.standard_normal((b, s)), jnp.float32)
    mask = jnp.asarray(
        (np.arange(s)[None] < lens[:, None]) & (rng.random((b, s)) > 0.3),
        jnp.float32)
    xp, mp = packing.pack(x, lens), packing.pack(mask, lens)
    # round trip is exact over the valid region
    np.testing.assert_array_equal(
        np.asarray(packing.pack(packing.unpack(xp, lens, s), lens)),
        np.asarray(xp))
    # any mask-weighted reduction agrees between layouts bit-for-bit is
    # too strict (summation order changes); fp32 tolerance is the contract
    np.testing.assert_allclose(float((x * mask).sum()),
                               float((xp * mp).sum()), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(mask.sum()), float(mp.sum()))

    # phantom bucketing never changes totals (phantoms carry mask 0)
    total = packing.bucket_total(int(cu[-1]), 8)
    xpad = jnp.pad(xp, (0, total - xp.shape[0]))
    mpad = jnp.pad(mp, (0, total - mp.shape[0]))
    np.testing.assert_allclose(float((xpad * mpad).sum()),
                               float((xp * mp).sum()), rtol=1e-5, atol=1e-5)


# ------------------------------------------------------- varlen attention

def _qkv(rng, t, hq=4, hkv=2, d=16):
    q = jnp.asarray(rng.standard_normal((t, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((t, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((t, hkv, d)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("lens", LENGTH_MIXES)
def test_varlen_matches_per_sequence_mha(lens):
    """The varlen oracle == plain mha_ref run on each sequence alone."""
    rng = np.random.default_rng(0)
    cu = packing.cu_seqlens_of(lens)
    q, k, v = _qkv(rng, int(cu[-1]))
    out = ops.varlen_mha(q, k, v, jnp.asarray(cu), max_seqlen=max(lens),
                         impl="reference")
    for i in range(len(lens)):
        lo, hi = int(cu[i]), int(cu[i + 1])
        solo = ref.mha_ref(q[lo:hi][None], k[lo:hi][None], v[lo:hi][None],
                           causal=True)[0]
        np.testing.assert_allclose(np.asarray(out[lo:hi]), np.asarray(solo),
                                   atol=1e-5)


@pytest.mark.parametrize("lens", LENGTH_MIXES)
def test_varlen_kernel_tier_parity(lens):
    """reference vs pallas_interpret agree to fp32 tolerance, including
    with phantom tail tokens and a banded reference."""
    rng = np.random.default_rng(1)
    cu = packing.cu_seqlens_of(lens)
    t = packing.bucket_total(int(cu[-1]), 16)  # phantom tail
    q, k, v = _qkv(rng, t)
    o_ref = ops.varlen_mha(q, k, v, jnp.asarray(cu), max_seqlen=max(lens),
                           impl="reference")
    o_int = ops.varlen_mha(q, k, v, jnp.asarray(cu), impl="pallas_interpret")
    valid = int(cu[-1])
    np.testing.assert_allclose(np.asarray(o_int[:valid]),
                               np.asarray(o_ref[:valid]), atol=1e-5)
    # phantom rows are unspecified but must stay finite in both tiers
    assert bool(jnp.isfinite(o_ref).all()) and bool(jnp.isfinite(o_int).all())


@pytest.mark.parametrize("impl", ["reference", "pallas_interpret"])
def test_varlen_no_cross_sequence_leakage(impl):
    """Perturb sequence j; every other sequence's outputs are
    bit-identical (hard NEG_INF masking, not additive masking)."""
    lens = [5, 9, 3]
    rng = np.random.default_rng(2)
    cu = packing.cu_seqlens_of(lens)
    q, k, v = _qkv(rng, int(cu[-1]))
    kw = dict(max_seqlen=max(lens)) if impl == "reference" else {}
    base = ops.varlen_mha(q, k, v, jnp.asarray(cu), impl=impl, **kw)
    j = 1
    sl = slice(int(cu[j]), int(cu[j + 1]))
    q2, k2, v2 = q.at[sl].add(3.0), k.at[sl].add(-2.0), v.at[sl].mul(5.0)
    pert = ops.varlen_mha(q2, k2, v2, jnp.asarray(cu), impl=impl, **kw)
    for i in (0, 2):
        osl = slice(int(cu[i]), int(cu[i + 1]))
        np.testing.assert_array_equal(np.asarray(base[osl]),
                                      np.asarray(pert[osl]))
    assert bool(jnp.any(base[sl] != pert[sl]))


def test_varlen_window_parity():
    lens = [7, 20, 4]
    rng = np.random.default_rng(3)
    cu = packing.cu_seqlens_of(lens)
    q, k, v = _qkv(rng, int(cu[-1]))
    o_ref = ops.varlen_mha(q, k, v, jnp.asarray(cu), window=5,
                           max_seqlen=max(lens), impl="reference")
    o_int = ops.varlen_mha(q, k, v, jnp.asarray(cu), window=5,
                           impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(o_int), np.asarray(o_ref),
                               atol=1e-5)
    solo = ref.mha_ref(q[7:27][None], k[7:27][None], v[7:27][None],
                       causal=True, window=5)[0]
    np.testing.assert_allclose(np.asarray(o_ref[7:27]), np.asarray(solo),
                               atol=1e-5)


# ------------------------------------------------------------- PPO parity

def _ppo_case(lens_gen, P=4, G=12, B=None, seed=0):
    """Build identical logical PPO inputs in both layouts.  ``lens_gen``
    are per-sequence *valid generated* token counts (1..G)."""
    g_valid = np.asarray(lens_gen)
    b = len(g_valid)
    S = P + G
    rng = np.random.default_rng(seed)
    toks = rng.integers(1, 500, (b, S)).astype(np.int32)
    gen_mask = (np.arange(G)[None] < g_valid[:, None]).astype(np.float32)
    logp = (rng.standard_normal((b, G)) * gen_mask).astype(np.float32)
    ref_logp = (rng.standard_normal((b, G)) * gen_mask).astype(np.float32)
    values = rng.standard_normal((b, G + 1)).astype(np.float32)
    rewards = rng.standard_normal(b).astype(np.float32)
    # packed layout keeps one post-EOS bootstrap token per sequence: the
    # GAE carry entering the last valid token is -V(its position)
    lens = P + np.minimum(g_valid + 1, G)
    return dict(P=P, G=G, S=S, toks=toks, gen_mask=gen_mask, logp=logp,
                ref_logp=ref_logp, values=values, rewards=rewards, lens=lens)


def _token_aligned(c):
    S, P = c["S"], c["P"]
    z = jnp.zeros((len(c["lens"]), S), jnp.float32)
    return {
        "logp": z.at[:, P:].set(jnp.asarray(c["logp"])),
        "ref_logp": z.at[:, P:].set(jnp.asarray(c["ref_logp"])),
        "mask": z.at[:, P:].set(jnp.asarray(c["gen_mask"])),
        "values": z.at[:, P - 1:].set(jnp.asarray(c["values"])),
        "old_values": z.at[:, P:].set(jnp.asarray(c["values"][:, :-1])),
    }


GEN_MIXES = [
    pytest.param([3, 12, 1, 5], id="long-tail"),
    pytest.param([1, 1, 1, 1], id="all-len-1"),
    pytest.param([7, 7, 7, 7], id="all-equal"),
    pytest.param([12], id="single-max"),
]


@pytest.mark.parametrize("gens", GEN_MIXES)
def test_packed_gae_matches_padded(gens):
    c = _ppo_case(gens)
    full = _token_aligned(c)
    shaped = PPO.shaped_rewards(HP, jnp.asarray(c["rewards"]),
                                jnp.asarray(c["logp"]),
                                jnp.asarray(c["ref_logp"]),
                                jnp.asarray(c["gen_mask"]))
    adv, ret = PPO.gae(HP, shaped, jnp.asarray(c["values"]),
                       jnp.asarray(c["gen_mask"]))
    lens = c["lens"]
    pk = lambda x: packing.pack(x, lens)
    cu = jnp.asarray(packing.cu_seqlens_of(lens))
    m_p, v_p = pk(full["mask"]), pk(full["values"])
    shaped_p = PPO.shaped_rewards_packed(
        HP, jnp.asarray(c["rewards"]), pk(full["logp"]),
        pk(full["ref_logp"]), m_p, cu)
    adv_p, ret_p = PPO.gae_packed(HP, shaped_p, PPO.packed_shift_right(v_p),
                                  v_p, m_p, cu)
    z = jnp.zeros((len(lens), c["S"]), jnp.float32)
    P = c["P"]
    for padded, packed in ((shaped, shaped_p), (adv, adv_p), (ret, ret_p)):
        np.testing.assert_allclose(
            np.asarray(pk(z.at[:, P:].set(padded))), np.asarray(packed),
            atol=1e-6)


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = ARCHS["qwen2-0.5b"].reduced()
    return cfg, MDL.init_params(jax.random.PRNGKey(0), cfg, head="lm")


@pytest.fixture(scope="module")
def tiny_value():
    cfg = ARCHS["qwen2-0.5b"].reduced()
    return cfg, MDL.init_params(jax.random.PRNGKey(1), cfg, head="value")


@pytest.mark.parametrize("gens", GEN_MIXES)
def test_packed_ppo_loss_and_grads_match_padded(gens, tiny_lm, tiny_value):
    """The headline contract: actor and critic loss AND param grads agree
    between layouts to fp32 tolerance on identical logical inputs."""
    cfg, params = tiny_lm
    vcfg, vparams = tiny_value
    c = _ppo_case(gens, seed=4)
    full = _token_aligned(c)
    lens, P, S = c["lens"], c["P"], c["S"]
    toksj = jnp.asarray(c["toks"])

    shaped = PPO.shaped_rewards(HP, jnp.asarray(c["rewards"]),
                                jnp.asarray(c["logp"]),
                                jnp.asarray(c["ref_logp"]),
                                jnp.asarray(c["gen_mask"]))
    adv, ret = PPO.gae(HP, shaped, jnp.asarray(c["values"]),
                       jnp.asarray(c["gen_mask"]))

    pk = lambda x: packing.pack(x, lens)
    cu = jnp.asarray(packing.cu_seqlens_of(lens))
    m_p, v_p = pk(full["mask"]), pk(full["values"])
    shaped_p = PPO.shaped_rewards_packed(
        HP, jnp.asarray(c["rewards"]), pk(full["logp"]),
        pk(full["ref_logp"]), m_p, cu)
    adv_p, ret_p = PPO.gae_packed(HP, shaped_p, PPO.packed_shift_right(v_p),
                                  v_p, m_p, cu)
    pb = packing.pack_batch(toksj, lens)
    batch_p = {"tokens": pb.tokens, "cu_seqlens": pb.cu_seqlens,
               "positions": pb.positions}

    def actor_padded(p):
        nl, _ = PPO.sequence_logprobs(p, cfg, toksj, P, remat=False)
        return PPO.actor_loss_fn(HP, nl, jnp.asarray(c["logp"]), adv,
                                 jnp.asarray(c["gen_mask"]))[0]

    def actor_packed(p):
        nl = PPO.packed_sequence_logprobs(p, cfg, batch_p, remat=False,
                                          max_seqlen=S)
        return PPO.actor_loss_fn(HP, nl, pk(full["logp"]), adv_p, m_p)[0]

    l1, g1 = jax.value_and_grad(actor_padded)(params)
    l2, g2 = jax.value_and_grad(actor_packed)(params)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    def critic_padded(p):
        v, _ = PPO.sequence_values(p, vcfg, toksj, P, remat=False)
        return PPO.critic_loss_fn(HP, v[:, :-1],
                                  jnp.asarray(c["values"][:, :-1]), ret,
                                  jnp.asarray(c["gen_mask"]))

    def critic_packed(p):
        v = PPO.packed_sequence_values(p, vcfg, batch_p, remat=False,
                                       max_seqlen=S)
        return PPO.critic_loss_fn(HP, PPO.packed_shift_right(v),
                                  pk(full["old_values"]), ret_p, m_p)

    l3, g3 = jax.value_and_grad(critic_padded)(vparams)
    l4, g4 = jax.value_and_grad(critic_packed)(vparams)
    np.testing.assert_allclose(float(l3), float(l4), rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(g3), jax.tree.leaves(g4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_pack_minibatches_groups_and_buckets():
    c = _ppo_case([3, 12, 1, 5], seed=7)
    full = _token_aligned(c)
    out = packing.pack_minibatches(
        jnp.asarray(c["toks"]), {"mask": full["mask"]}, c["lens"], 2,
        bucket=16)
    assert out["tokens"].shape[0] == 2
    assert out["tokens"].shape[1] % 16 == 0
    assert out["cu_seqlens"].shape == (2, 3)
    # per-group mask totals match the contiguous padded grouping
    gm = c["gen_mask"]
    np.testing.assert_allclose(np.asarray(out["mask"][0]).sum(),
                               gm[:2].sum())
    np.testing.assert_allclose(np.asarray(out["mask"][1]).sum(),
                               gm[2:].sum())


def test_packed_training_experiment_end_to_end():
    """ExperimentConfig.packed_training: one full PPO iteration through the
    engine runs and updates both trainables with finite stats."""
    from repro.core.plan import Cluster
    from repro.rlhf.experiment import ExperimentConfig, RLHFExperiment
    actor = ARCHS["qwen2-0.5b"].reduced()
    cfg = ExperimentConfig(batch=4, prompt_len=8, gen_len=8, eos_id=3,
                           packed_training=True,
                           ppo=PPO.PPOHyperparameters(n_minibatches=2))
    e = RLHFExperiment(actor, actor, Cluster(n_nodes=1, devs_per_node=1),
                       cfg, search=False)
    p0 = jax.tree.map(np.asarray, e.models["actor"].params)
    out = e.run_iteration(jax.random.PRNGKey(0))
    assert np.isfinite(out["actor_stats"]["loss"])
    assert np.isfinite(out["critic_stats"]["loss"])
    delta = sum(float(np.abs(np.asarray(a) - b).sum()) for a, b in
                zip(jax.tree.leaves(e.models["actor"].params),
                    jax.tree.leaves(p0)))
    assert delta > 0
    # the graph advertises real token counts for the train calls
    trn = e.graph.by_name["actor_train"].workload
    assert trn.total_tokens == cfg.batch * (cfg.prompt_len + cfg.gen_len)


def test_packed_rejects_recurrent_mixers():
    cfg = ARCHS["mamba2-1.3b"].reduced()
    params = MDL.init_params(jax.random.PRNGKey(0), cfg, head="lm")
    pb = packing.pack_batch(jnp.ones((2, 8), jnp.int32), [4, 6])
    with pytest.raises(NotImplementedError):
        MDL.forward(params, cfg, {"tokens": pb.tokens,
                                  "cu_seqlens": pb.cu_seqlens,
                                  "positions": pb.positions}, remat=False)
