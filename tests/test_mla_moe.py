"""DeepSeek-V2's parts in the program, at a small size on the CPU:
multi-head latent attention (expanded for full sequences, absorbed over
the latent cache in decode) with YaRN RoPE, and the expert layer that holds
a share of the router's experts beside a shared expert.

Weights are seeded and float32, so the forms compared here differ only by
the order of the same reductions: tolerances are set from that (about 1e-5
of the values' scale) with room, far below what a missing or extra part of
the mathematics changes (0.01 and up).
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import model as MDL
from repro.models import moe as M
from repro.models import transformer as T

KEY = jax.random.PRNGKey(16)
init_params = jax.jit(MDL.init_params, static_argnums=1)


def tiny(**kw):
    """deepseek-v2-lite reduced: a dense first layer, two MoE layers of 4
    routed experts (top 2) and one shared expert, MLA 4 x (16 + 8) over a
    32-wide latent, YaRN as published."""
    return ARCHS["deepseek-v2-lite"].reduced(**kw)


# ------------------------------------------------------------------ MLA

@pytest.mark.parametrize("share", [False, True], ids=["whole", "share"])
def test_absorbed_decode_matches_expanded_forward_logits(share):
    """Prefill through the expanded MLA, then absorbed decode steps over the
    latent cache, give the logits of the expanded full forward at every
    position (expert layer whole, or holding 2 of 4 from expert 1)."""
    cfg = tiny(**(dict(n_local_experts=2, expert_offset=1) if share else {}))
    p = init_params(KEY, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (3, 14), 0,
                              cfg.vocab_size)
    full = jax.jit(lambda p, t: MDL.logits_of(
        p, cfg, MDL.forward(p, cfg, {"tokens": t})[0]))(p, toks)
    prompt = 6
    last, caches, _ = jax.jit(lambda p, t: MDL.prefill(
        p, cfg, {"tokens": t}, 14))(p, toks[:, :prompt])
    assert caches[0]["b0"]["latent"].shape == (1, 3, 14, 40)  # r + dr
    got = [MDL.logits_of(p, cfg, last[:, None])[:, 0]]
    step = jax.jit(lambda tok, c, t: MDL.decode_step(p, cfg, tok, c, t))
    for t in range(prompt, 14):
        lg, caches, _ = step(toks[:, t], caches, jnp.int32(t))
        got.append(lg)
    got = jnp.stack(got, 1)
    scale = float(jnp.max(jnp.abs(full)))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(full[:, prompt - 1:]),
                               atol=1e-4 * scale)


def test_mla_scale_and_rope_enter_the_scores():
    """The softmax scale is 192**-0.5 x YaRN's temperature squared, and
    the decode reads the cached k_pe as roped: a cache whose rope columns
    are zeroed changes the output."""
    full = ARCHS["deepseek-v2-lite"]
    m = 0.1 * 0.707 * math.log(40) + 1
    assert MLA.softmax_scale(full) == pytest.approx(192 ** -0.5 * m * m)
    cfg = tiny()
    p = init_params(KEY, cfg)
    mixer = jax.tree.map(lambda a: a[0], p["groups"][1]["b0"]["mixer"])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, cfg.d_model))
    pos = jnp.arange(5)[None]
    _, lat = jax.jit(lambda m, x: MLA.mla_apply(m, cfg, x, pos,
                                                 want_latent=True))(mixer, x)
    cache = {"latent": jnp.zeros((1, 2, 6, 40)).at[0, :, :5].set(lat)}
    xt = jax.random.normal(jax.random.PRNGKey(3), (2, 1, cfg.d_model))
    decode = jax.jit(lambda m, x, c: MLA.mla_decode_apply(
        m, cfg, x, c, jnp.int32(5), 0))
    y1, new = decode(mixer, xt, cache)
    no_pe = {"latent": cache["latent"].at[..., 32:].set(0.0)}
    y2, _ = decode(mixer, xt, no_pe)
    assert float(jnp.max(jnp.abs(y1 - y2))) > 1e-3
    # the step wrote exactly its own token
    assert np.array_equal(np.asarray(new["latent"][0, :, :5]),
                          np.asarray(cache["latent"][0, :, :5]))
    assert float(jnp.max(jnp.abs(new["latent"][0, :, 5]))) > 0


def test_yarn_frequencies_pinned():
    """DeepSeek-V2-Lite's YaRN over the 64 rope dims: correction pairs 10
    to 23, theta**(-2i/64) below, the same over 40 above, linear in
    between."""
    assert L.yarn_correction_range(64, 1e4, 4096, 32, 1) == (10, 23)
    f = L.rope_freqs(ARCHS["deepseek-v2-lite"], 64)
    extra = 1e4 ** (-np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(f, extra * (1 - ramp) + extra / 40 * ramp,
                               rtol=1e-6)
    np.testing.assert_allclose(f[:11], extra[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], extra[23:] / 40, rtol=1e-6)
    assert L.rope_freqs(ARCHS["qwen3-1.7b"], 128) is None  # plain RoPE


# ------------------------------------------------------------ the experts

def _layer(cfg, key):
    return jax.jit(M.moe_init, static_argnums=1)(key, cfg)


def test_expert_shares_sum_to_the_whole_layer():
    """Four chips' shares of an 8-expert layer (2 experts each), with the
    shared expert that every chip computes counted once, add up to the
    uncut layer that holds all 8; the router keeps 8 outputs in every
    share."""
    whole = tiny(n_experts=8, top_k=3)
    p = _layer(whole, KEY)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, whole.d_model))
    want, _, _ = jax.jit(lambda p, x: M.moe_apply(p, whole, x))(p, x)
    shared = L.mlp_apply(p["shared"], whole, x)
    total = jnp.zeros_like(want)
    held = 0
    for off in range(0, 8, 2):
        cfg = dataclasses.replace(whole, n_local_experts=2,
                                  expert_offset=off)
        ps = dict(p, **{k: p[k][off:off + 2]
                        for k in ("w_gate", "w_in", "w_out")})
        assert ps["router"]["w"].shape[-1] == 8
        y, _, c = jax.jit(lambda p, x: M.moe_apply(p, cfg, x))(  # noqa: B023
            ps, x)
        total = total + y - shared
        held += int(c["held"])
        assert 0 <= int(c["held_max"]) <= int(c["held"])
        assert int(c["routed"]) == 2 * 9 * 3
    total = total + shared
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5 * scale)
    assert held == 2 * 9 * 3  # every assignment lands on exactly one share
    # one share alone is not the layer
    assert float(jnp.max(jnp.abs(y - want))) > 1e-2 * scale


def test_norm_topk_prob_false_keeps_the_router_weights():
    """Without renormalisation each token's routed part is the
    renormalised one times the sum of its top-k probabilities."""
    base = tiny(n_experts=8, top_k=3, n_shared_experts=0)
    p = _layer(base, KEY)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 7, base.d_model))

    def apply(norm):
        cfg = dataclasses.replace(base, norm_topk_prob=norm)
        return jax.jit(lambda p, x: M.moe_apply(p, cfg, x)[0])(p, x)
    y_norm, y_raw = apply(True), apply(False)
    probs = jax.nn.softmax(x.reshape(-1, base.d_model) @ p["router"]["w"], -1)
    mass = jax.lax.top_k(probs, 3)[0].sum(-1).reshape(2, 7, 1)
    assert float(jnp.min(mass)) < 0.99
    np.testing.assert_allclose(np.asarray(y_raw), np.asarray(y_norm * mass),
                               atol=1e-5 * float(jnp.max(jnp.abs(y_norm))))
    # granite and arctic renormalise, as before
    assert ARCHS["granite-moe-1b-a400m"].norm_topk_prob
    assert ARCHS["arctic-480b"].norm_topk_prob
    assert not ARCHS["deepseek-v2-lite"].norm_topk_prob


def test_decode_loop_counts_the_forwards_routing():
    """The routing counters that generate sums in its decode loop's carry
    equal those of one forward over the same tokens: the prompt and every
    generated token but the last, which is sampled and never fed."""
    cfg = tiny(n_local_experts=2, expert_offset=2)
    p = init_params(KEY, cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(6), (2, 5), 0,
                                cfg.vocab_size)
    out = jax.jit(lambda p, t: MDL.generate(
        p, cfg, {"tokens": t}, num_new_tokens=6,
        rng=jax.random.PRNGKey(7)))(p, prompt)
    fed = jnp.concatenate([prompt, out["tokens"][:, :-1]], axis=1)
    counts = jax.jit(lambda p, t: MDL.forward(p, cfg, {"tokens": t})[2])(
        p, fed)
    moe = jax.tree.map(np.asarray, out["moe"])
    assert moe["routed"].tolist() == [2 * 10 * 2] * 2  # 2 MoE layers
    np.testing.assert_array_equal(moe["held"], np.asarray(counts["held"]))
    assert 0 < moe["held"].sum() < moe["routed"].sum()
    # the busiest expert of each step, summed over the prefill and 5 steps,
    # is at least that of the whole sequence
    assert np.all(moe["held_max"] >= np.asarray(counts["held_max"]))
    assert np.all(moe["held_max"] <= moe["held"])


def test_chunked_dispatch_matches_one_chunk(monkeypatch):
    """A cohort dispatched in token chunks gives each token the result one
    dispatch gives it, and the same gradients."""
    cfg = tiny(n_local_experts=2, expert_offset=1)
    p = _layer(cfg, KEY)
    x = jax.random.normal(jax.random.PRNGKey(8), (4, 96, cfg.d_model))

    def loss(p, x):
        y, _, _ = M.moe_apply(p, cfg, x)
        return jnp.sum(jnp.sin(y)), y

    grad = jax.value_and_grad(loss, has_aux=True)
    (_, one), g_one = jax.jit(grad)(p, x)
    monkeypatch.setattr(M, "DISPATCH_CHUNK", 64)
    assert L.even_chunks(384, 1, M.DISPATCH_CHUNK) == 6
    (_, many), g_many = jax.jit(grad)(p, x)
    np.testing.assert_allclose(np.asarray(many), np.asarray(one), atol=1e-5)
    for a, b in zip(jax.tree.leaves(g_many), jax.tree.leaves(g_one)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("where", ["attention", "ffn"])
def test_chunked_training_forward_matches_one_chunk(monkeypatch, where):
    """The reference tier's MLA attention in batch chunks, or the dense
    first layer's FFN in token chunks, gives the whole forward's logits and
    gradients."""
    cfg = tiny()
    p = init_params(KEY, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(9), (4, 16), 0,
                              cfg.vocab_size)

    def loss(p):
        h = MDL.forward(p, cfg, {"tokens": toks})[0]
        return jnp.sum(jnp.sin(MDL.logits_of(p, cfg, h)))

    grad = jax.jit(jax.value_and_grad(loss))
    whole, g_whole = grad(p)
    if where == "attention":  # one sequence's scores a chunk
        monkeypatch.setattr(MLA, "SCORE_CHUNK_BYTES",
                            cfg.n_heads * 16 * 16 * 4)
        assert L.even_chunks(4, cfg.n_heads * 16 * 16 * 4,
                             MLA.SCORE_CHUNK_BYTES) == 4
    else:  # 16 tokens a chunk
        monkeypatch.setattr(T, "FFN_CHUNK_ACTIVATIONS", 16 * cfg.d_ff)
        assert L.even_chunks(64, cfg.d_ff, T.FFN_CHUNK_ACTIVATIONS) == 4
    grad = jax.jit(jax.value_and_grad(loss))
    chunked, g_chunked = grad(p)
    np.testing.assert_allclose(float(chunked), float(whole), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g_chunked), jax.tree.leaves(g_whole)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
