"""Per-architecture smoke tests: reduced configs, one forward/train step on
CPU, asserting output shapes + finite values; decode == full-forward
consistency for every family."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, ASSIGNED
from repro.models import (decode_step, forward, generate, init_params,
                          logits_of, lm_loss, prefill, synth_batch, values_of)
from repro.optim import adamw
from repro.parallel.steps import make_train_step

RNG = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def reduced():
    return {a: ARCHS[a].reduced() for a in ASSIGNED}


@pytest.mark.parametrize("arch", ASSIGNED)
def test_forward_shapes_and_finite(arch, reduced):
    cfg = reduced[arch]
    p = init_params(RNG, cfg)
    batch = synth_batch(RNG, cfg, 32, 2, "train")
    h, aux = forward(p, cfg, batch, remat=False)
    assert h.shape == (2, 32, cfg.d_model)
    assert bool(jnp.all(jnp.isfinite(h)))
    logits = logits_of(p, cfg, h)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch", ASSIGNED)
def test_train_step_no_nans(arch, reduced):
    cfg = reduced[arch]
    p = init_params(RNG, cfg)
    opt_cfg = adamw.AdamWConfig(lr=1e-3)
    opt = adamw.init(opt_cfg, p)
    batch = synth_batch(RNG, cfg, 16, 2, "train")
    step = jax.jit(make_train_step(cfg, opt_cfg, remat=True))
    p2, opt2, metrics = step(p, opt, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # params actually changed
    delta = sum(float(jnp.sum(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32))))
                for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p2)))
    assert delta > 0


@pytest.mark.parametrize("arch", ASSIGNED)
def test_decode_matches_forward(arch, reduced):
    """Stepwise decode from a mid-sequence prefill reproduces the
    full-sequence forward logits.

    This includes the MoE configs (arctic/granite): the default
    ``moe_dispatch="dropless"`` routes every token through exactly its own
    top-k experts with row-local combine weights, so routing no longer
    depends on the cohort the token is computed in.  (The legacy
    ``"capacity"`` dispatch is cohort-dependent — ``capacity(B*S)`` scales
    with the total token count and drop rank spans the batch-major flat
    cohort — and cannot pass this test when an expert overflows; see
    ``tests/test_moe.py`` for its drop/renormalization semantics.)"""
    cfg = reduced[arch]
    p = init_params(RNG, cfg)
    S = 24
    batch = synth_batch(RNG, cfg, S, 2, "prefill")
    h, _ = forward(p, cfg, batch, remat=False)
    full_logits = logits_of(p, cfg, h)
    cut = S - 4
    pb = {k: (v[:, :cut] if k == "tokens" else v) for k, v in batch.items()}
    last_h, caches = prefill(p, cfg, pb, max_len=S)
    lg = logits_of(p, cfg, last_h[:, None])[:, 0]
    errs = [float(jnp.max(jnp.abs(lg - full_logits[:, cut - 1])))]
    for t in range(cut, S - 1):
        lg, caches = decode_step(p, cfg, batch["tokens"][:, t], caches,
                                 jnp.int32(t))
        errs.append(float(jnp.max(jnp.abs(lg - full_logits[:, t]))))
    assert max(errs) < 5e-4, errs


def test_generate_shapes():
    cfg = ARCHS["qwen2-0.5b"].reduced()
    p = init_params(RNG, cfg)
    batch = synth_batch(RNG, cfg, 8, 2, "prefill")
    out = generate(p, cfg, batch, num_new_tokens=5, rng=RNG)
    assert out["tokens"].shape == (2, 5)
    assert out["logprobs"].shape == (2, 5)
    assert bool(jnp.all(out["logprobs"] <= 0))


@pytest.mark.parametrize(
    "arch", [a for a in ASSIGNED if ARCHS[a].tie_embeddings])
def test_sampled_logprobs_at_init_are_not_degenerate(arch, reduced):
    """A tied table doubles as the LM head: drawn at the head's scale the
    logits are of unit order, so tokens sampled from a fresh model carry
    logprobs near -log(vocab).  Drawn at std 1 they sit near 0, which
    leaves every rollout-vs-reference logprob check empty."""
    cfg = reduced[arch]
    p = init_params(RNG, cfg)
    batch = synth_batch(RNG, cfg, 8, 4, "prefill")
    out = generate(p, cfg, batch, num_new_tokens=8, rng=RNG)
    mean = float(out["logprobs"].mean())
    assert mean < -0.5 * np.log(cfg.vocab_size), mean


def test_value_head():
    cfg = ARCHS["qwen2-0.5b"].reduced()
    p = init_params(RNG, cfg, head="value")
    batch = synth_batch(RNG, cfg, 8, 2, "prefill")
    h, _ = forward(p, cfg, batch, remat=False)
    v = values_of(p, h)
    assert v.shape == (2, 8)
    assert bool(jnp.all(jnp.isfinite(v)))


def test_vlm_prefix_masking():
    """internvl2: prefix positions carry patch embeddings, loss masks them."""
    cfg = ARCHS["internvl2-76b"].reduced()
    assert cfg.prefix_len > 0
    p = init_params(RNG, cfg)
    batch = synth_batch(RNG, cfg, 16, 2, "train")
    assert batch["prefix_embeds"].shape == (2, cfg.prefix_len, cfg.d_model)
    assert float(batch["mask"][:, :cfg.prefix_len].sum()) == 0.0
    loss, _ = lm_loss(p, cfg, batch, remat=False)
    assert bool(jnp.isfinite(loss))


def test_encdec_uses_encoder():
    """seamless: changing the audio frames must change decoder logits."""
    cfg = ARCHS["seamless-m4t-medium"].reduced()
    p = init_params(RNG, cfg)
    batch = synth_batch(RNG, cfg, 8, 1, "prefill")
    h1, _ = forward(p, cfg, batch, remat=False)
    batch2 = dict(batch, frames=batch["frames"] + 1.0)
    h2, _ = forward(p, cfg, batch2, remat=False)
    assert float(jnp.max(jnp.abs(h1 - h2))) > 1e-4


def test_window_attention_ignores_distant_tokens():
    """gemma3 local layers: a token beyond every window cannot influence the
    last position if all layers are local (use a pure-local reduced cfg)."""
    import dataclasses
    from repro.configs.base import ATTN, LayerSpec
    base = ARCHS["gemma3-1b"].reduced()
    cfg = dataclasses.replace(
        base, superblock=(LayerSpec(ATTN, window=4),), n_superblocks=2,
        tail=(), num_layers=2)
    p = init_params(RNG, cfg)
    toks = jax.random.randint(RNG, (1, 32), 0, cfg.vocab_size, jnp.int32)
    h1, _ = forward(p, cfg, {"tokens": toks}, remat=False)
    toks2 = toks.at[:, 0].set((toks[:, 0] + 1) % cfg.vocab_size)
    h2, _ = forward(p, cfg, {"tokens": toks2}, remat=False)
    # position 0 is > 2*window away from the last position with 2 layers
    np.testing.assert_allclose(np.asarray(h1[:, -1]), np.asarray(h2[:, -1]),
                               atol=1e-5)
