"""Model facade: init / train-forward / prefill / decode / generate for every
architecture family, plus ``input_specs`` (ShapeDtypeStruct stand-ins) for the
dry-run.

Conventions
-----------
* decoder-only:  batch = {"tokens": (B,S) int32, "labels": (B,S) int32,
  "mask": (B,S) f32}.  [vlm] archs add {"prefix_embeds": (B,P,D)} — the
  frontend stub — and the first P positions of tokens/labels are ignored.
* enc-dec ([audio]): {"frames": (B,P,D)} feed the encoder; tokens drive the
  decoder.
* value models (RLHF critic/reward) share the trunk; ``head="value"`` swaps
  the LM head for a scalar head.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import attention as A
from repro.models import layers as L
from repro.models import transformer as T
from repro.parallel import ctx


# ----------------------------------------------------------------- init

def init_params(key, cfg: ModelConfig, head: str = "lm"):
    ks = jax.random.split(key, 6)
    dt = jnp.dtype(cfg.dtype)
    p = {
        "embed": L.embed_init(ks[0], cfg),
        "groups": T.stack_init(ks[1], cfg, cross=(cfg.family == "encdec")),
        "final_norm": L.rmsnorm_init(cfg.d_model, dt),
    }
    if head == "lm":
        if not cfg.tie_embeddings:
            p["lm_head"] = L.dense_init(ks[2], cfg.d_model, cfg.vocab_size, dt)
    else:
        p["value_head"] = L.dense_init(ks[2], cfg.d_model, 1, jnp.float32)
    if cfg.family == "encdec":
        p["encoder"] = {
            "groups": T.stack_init(ks[3], cfg, cross=False),
            "final_norm": L.rmsnorm_init(cfg.d_model, dt),
        }
    return p


# ----------------------------------------------------------------- forward

def _encode(params, cfg: ModelConfig, frames, impl):
    pos = jnp.arange(frames.shape[1])[None, :]
    h, _, _ = T.stack_apply(params["encoder"]["groups"], cfg, frames, pos,
                            causal=False, impl=impl)
    return L.rmsnorm_apply(params["encoder"]["final_norm"], h, cfg.norm_eps)


def _embed_inputs(params, cfg: ModelConfig, batch):
    """Token embedding with optional [vlm] prefix splice."""
    x = L.embed_apply(params["embed"], batch["tokens"]).astype(cfg.dtype)
    x = ctx.constrain(x, ctx.BATCH, None, None)
    if cfg.prefix_len and cfg.family != "encdec":
        pe = batch["prefix_embeds"].astype(cfg.dtype)
        x = jnp.concatenate([pe, x[:, cfg.prefix_len:]], axis=1)
    return x


def forward(params, cfg: ModelConfig, batch, *, impl="reference", remat=True,
            max_seqlen=None):
    """Full-sequence forward.  Returns (hidden (B,S,D), aux_loss, the MoE
    layers' routing counters ({} for models without MoE)).

    Packed mode: when ``batch`` has "cu_seqlens", its "tokens" are a (T,)
    packed cohort and "positions" the (T,) within-sequence positions.  The
    cohort flows through the stack as one (1, T, D) row — norms/FFN/MoE
    are per-token, attention goes block-diagonal via varlen_mha — and the
    returned hidden is (1, T, D).  ``max_seqlen`` (static: the longest
    sequence) keys the banded varlen reference; pass it whenever known."""
    if "cu_seqlens" in batch:
        assert cfg.family != "encdec" and not cfg.prefix_len, \
            "packed training supports decoder-only, prefix-free configs"
        x = L.embed_apply(params["embed"],
                          batch["tokens"][None]).astype(cfg.dtype)
        x = ctx.constrain(x, ctx.BATCH, None, None)
        h, aux, counts = T.stack_apply(
            params["groups"], cfg, x, batch["positions"][None], causal=True,
            impl=impl, remat=remat, cu_seqlens=batch["cu_seqlens"],
            max_seqlen=max_seqlen)
        return (L.rmsnorm_apply(params["final_norm"], h, cfg.norm_eps), aux,
                counts)
    x = _embed_inputs(params, cfg, batch)
    pos = jnp.arange(x.shape[1])[None, :]
    enc_out = None
    if cfg.family == "encdec":
        enc_out = _encode(params, cfg, batch["frames"], impl)
    h, aux, counts = T.stack_apply(params["groups"], cfg, x, pos, causal=True,
                                   impl=impl, enc_out=enc_out, remat=remat)
    return L.rmsnorm_apply(params["final_norm"], h, cfg.norm_eps), aux, counts


def logits_of(params, cfg: ModelConfig, hidden):
    logits = L.unembed_apply(params.get("lm_head"), params["embed"], hidden,
                             tie=cfg.tie_embeddings)
    return ctx.constrain(logits, ctx.BATCH, None, ctx.TP)


def values_of(params, hidden):
    return L.dense_apply(params["value_head"],
                         hidden.astype(jnp.float32))[..., 0]


def lm_loss(params, cfg: ModelConfig, batch, *, impl="reference", remat=True,
            aux_weight=0.01):
    hidden, aux, _ = forward(params, cfg, batch, impl=impl, remat=remat)
    head_fn = lambda h: logits_of(params, cfg, h)
    loss, _ = L.chunked_lm_head_loss(head_fn, hidden, batch["labels"],
                                     batch["mask"])
    return loss + aux_weight * aux, {"lm_loss": loss, "aux_loss": aux}


# ----------------------------------------------------------------- serving

def prefill(params, cfg: ModelConfig, batch, max_len, *, impl="reference"):
    """Run the prompt, fill caches, return (last_hidden (B,D), caches, the
    routing counters ({} for models without MoE))."""
    x = _embed_inputs(params, cfg, batch)
    pos = jnp.arange(x.shape[1])[None, :]
    enc_out = None
    cross = cfg.family == "encdec"
    enc_len = None
    if cross:
        enc_out = _encode(params, cfg, batch["frames"], impl)
        enc_len = enc_out.shape[1]
    caches = T.cache_init(cfg, x.shape[0], max_len, jnp.dtype(cfg.dtype),
                          cross=cross, enc_len=enc_len)
    h, caches, counts = T.stack_prefill(params["groups"], cfg, x, pos, caches,
                                        impl=impl, enc_out=enc_out)
    h = L.rmsnorm_apply(params["final_norm"], h, cfg.norm_eps)
    return h[:, -1], caches, counts


def decode_step(params, cfg: ModelConfig, token, caches, t, *,
                impl="reference"):
    """token: (B,) int32; t: scalar int32 (position of this token).
    Returns (logits (B,V) fp32, new_caches, the step's routing counters)."""
    x = L.embed_apply(params["embed"], token[:, None]).astype(cfg.dtype)
    cross = cfg.family == "encdec"
    h, caches, counts = T.stack_decode(params["groups"], cfg, x, caches, t,
                                       impl=impl, cross=cross)
    h = L.rmsnorm_apply(params["final_norm"], h, cfg.norm_eps)
    logits = logits_of(params, cfg, h)[:, 0]
    return logits, caches, counts


def decode_and_sample_step(params, cfg: ModelConfig, token, caches, t, key,
                           *, temperature: float = 1.0, sampler: str = "cdf",
                           top_k: int = 0, top_p: float = 1.0,
                           impl="reference"):
    """Fused decode + sample: one decode step on ``token`` followed by
    sampling the *next* token and its logprob from the produced logits,
    without materializing a full ``log_softmax`` (``ops.sample_logits``,
    including fused top-k/top-p truncation).  ``key=None`` means greedy.
    Returns (next_token (B,), logprob (B,), new_caches, the step's routing
    counters) — nothing vocab-sized escapes this function."""
    logits, caches, counts = decode_step(params, cfg, token, caches, t,
                                         impl=impl)
    tok, lp = ops.sample_logits(logits, key, temperature=temperature,
                                sampler=sampler, top_k=top_k, top_p=top_p,
                                impl=impl)
    return tok, lp, caches, counts


def paged_decode_and_sample_step(params, cfg: ModelConfig, token, caches,
                                 block_table, positions, key, *,
                                 temperature: float = 1.0,
                                 sampler: str = "cdf", top_k: int = 0,
                                 top_p: float = 1.0, impl="reference"):
    """Fused decode + sample over paged caches with per-row positions.

    token: (B,) the token each row consumes this step; positions: (B,) its
    per-row position (rows advance independently — the continuous-batching
    decode step); block_table: (B, M) physical block ids.  Returns
    (next_token (B,), logprob (B,), new_caches)."""
    x = L.embed_apply(params["embed"], token[:, None]).astype(cfg.dtype)
    h, caches = T.stack_paged_decode(params["groups"], cfg, x, caches,
                                     block_table, positions, impl=impl)
    h = L.rmsnorm_apply(params["final_norm"], h, cfg.norm_eps)
    logits = logits_of(params, cfg, h)[:, 0]
    tok, lp = ops.sample_logits(logits, key, temperature=temperature,
                                sampler=sampler, top_k=top_k, top_p=top_p,
                                impl=impl)
    return tok, lp, caches


def paged_draft_step(params, cfg: ModelConfig, token, caches, block_table,
                     positions, key, *, temperature: float = 1.0,
                     sampler: str = "cdf", top_k: int = 0, top_p: float = 1.0,
                     impl="reference"):
    """Draft-model decode step: like :func:`paged_decode_and_sample_step`
    but also returns the full (B, V) logits — the verify step's residual
    resampling needs the draft's proposal distribution, not just the
    sampled token.  Returns (next_token (B,), logits (B, V) f32, caches)."""
    x = L.embed_apply(params["embed"], token[:, None]).astype(cfg.dtype)
    h, caches = T.stack_paged_decode(params["groups"], cfg, x, caches,
                                     block_table, positions, impl=impl)
    h = L.rmsnorm_apply(params["final_norm"], h, cfg.norm_eps)
    logits = logits_of(params, cfg, h)[:, 0].astype(jnp.float32)
    tok, _ = ops.sample_logits(logits, key, temperature=temperature,
                               sampler=sampler, top_k=top_k, top_p=top_p,
                               impl=impl)
    return tok, logits, caches


def paged_verify_step(params, cfg: ModelConfig, tokens, caches, block_table,
                      positions, *, impl="reference"):
    """Speculative verify-step forward: score a whole draft window in one
    prefill-shaped dispatch against the paged KV cache.

    tokens: (B, K) — the last committed token followed by the draft's
    proposals; positions: (B, K) their absolute per-row positions.  Every
    token's KV is appended to the paged pool and position i's returned
    logits are the target's next-token distribution after consuming
    tokens[:, :i+1] — bit-consistent with i single-token decode steps (the
    rejection-sampling invariant rests on this).  Returns
    (logits (B, K, V) f32, caches)."""
    x = L.embed_apply(params["embed"], tokens).astype(cfg.dtype)
    h, caches = T.stack_paged_verify(params["groups"], cfg, x, caches,
                                     block_table, positions, impl=impl)
    h = L.rmsnorm_apply(params["final_norm"], h, cfg.norm_eps)
    return logits_of(params, cfg, h).astype(jnp.float32), caches


def generate(params, cfg: ModelConfig, batch, *, num_new_tokens: int,
             rng=None, temperature: float = 1.0, impl="reference",
             fused: bool = True, eos_id: int | None = None,
             sampler: str = "cdf", top_k: int = 0, top_p: float = 1.0):
    """Greedy/sampled autoregressive generation after a prefill.

    Returns dict with tokens (B, T_new), logprobs (B, T_new), caches.
    The decode loop is a single compiled ``lax.scan`` — the TPU analogue of
    the paper's CUDAGraph decode (no per-token dispatch).

    With ``fused`` (the default) sampling and logprob extraction happen
    inside the decode step, so the scan carries a (B,) token instead of a
    (B, V) logits array, never recomputes ``log_softmax``, and skips the
    seed loop's trailing wasted decode (the returned caches therefore do
    not contain the last sampled token's KV — no consumer attends to it).
    With ``sampler="gumbel"`` tokens and logprobs are identical to the
    unfused path for the same ``rng``; the default ``"cdf"`` sampler draws
    equally-exact samples far cheaper (one uniform per row instead of a
    (B, V) Gumbel field — see ``ops.sample_logits``).  ``fused=False``
    keeps the original loop for comparison.

    With ``eos_id`` set (fused only), the scan is replaced by an
    EOS-early-exit ``lax.while_loop``: once a row emits ``eos_id`` its
    remaining tokens are forced to ``eos_id`` with logprob 0, and the loop
    exits as soon as every row is done.  The result gains a ``gen_mask``
    entry ((B, T_new) f32, 1.0 through each row's first EOS).

    ``top_k`` / ``top_p`` truncate the sampling distribution inside the
    fused sampler (mask-then-renormalize, see ``ops.sample_logits``);
    returned logprobs stay full-distribution (PPO convention).

    For MoE models the fused loops also return ``moe``: the routing
    counters of the prefill and every decode step, summed in the loop's
    carry ({"routed", "held", "held_max"}, each (L,); ``held_max`` sums
    each step's busiest held expert).
    """
    if eos_id is not None and not fused:
        raise ValueError("eos_id requires the fused decode loop "
                         "(fused=True); the legacy loop has no EOS exit")
    if (top_k or top_p < 1.0) and not fused:
        raise ValueError("top_k/top_p truncation requires the fused "
                         "sampler (fused=True)")
    prompt_len = batch["tokens"].shape[1]
    max_len = prompt_len + num_new_tokens
    last_h, caches, moe0 = prefill(params, cfg, batch, max_len, impl=impl)
    logits0 = logits_of(params, cfg, last_h[:, None])[:, 0]

    keys = (jax.random.split(rng, num_new_tokens) if rng is not None
            else jnp.zeros((num_new_tokens, 2), jnp.uint32))

    if not fused:
        def sample(lg, key):
            lg = lg / jnp.maximum(temperature, 1e-6)
            if rng is None:
                return jnp.argmax(lg, axis=-1).astype(jnp.int32)
            return jax.random.categorical(key, lg, axis=-1).astype(jnp.int32)

        def logp_of(lg, tok):
            lp = jax.nn.log_softmax(lg, axis=-1)
            return jnp.take_along_axis(lp, tok[:, None], axis=-1)[:, 0]

        def body(carry, key):
            logits, caches, t = carry
            tok = sample(logits, key)
            lp = logp_of(logits, tok)
            new_logits, caches, _ = decode_step(params, cfg, tok, caches, t,
                                                impl=impl)
            return (new_logits, caches, t + 1), (tok, lp)

        (_, caches, _), (toks, lps) = jax.lax.scan(
            body, (logits0, caches, jnp.int32(prompt_len)), keys)
        return {"tokens": toks.T, "logprobs": lps.T, "caches": caches}

    tok0, lp0 = ops.sample_logits(logits0, keys[0] if rng is not None else
                                  None, temperature=temperature,
                                  sampler=sampler, top_k=top_k, top_p=top_p,
                                  impl=impl)

    def step(tok, caches, t, key, moe):
        """One fused decode step; adds its routing counters to ``moe``."""
        ntok, lp, caches, counts = decode_and_sample_step(
            params, cfg, tok, caches, t, key, temperature=temperature,
            sampler=sampler, top_k=top_k, top_p=top_p, impl=impl)
        return ntok, lp, caches, jax.tree.map(jnp.add, moe, counts)

    if eos_id is None:
        def body(carry, key):
            tok, caches, t, moe = carry
            ntok, lp, caches, moe = step(
                tok, caches, t, key if rng is not None else None, moe)
            return (ntok, caches, t + 1, moe), (ntok, lp)

        (_, caches, _, moe), (toks, lps) = jax.lax.scan(
            body, (tok0, caches, jnp.int32(prompt_len), moe0), keys[1:])
        tokens = jnp.concatenate([tok0[None], toks], axis=0).T
        logprobs = jnp.concatenate([lp0[None], lps], axis=0).T
        res = {"tokens": tokens, "logprobs": logprobs, "caches": caches}
        return {**res, "moe": moe} if moe else res

    # EOS-early-exit variant: fixed-shape (B, T) buffers, dynamic trip count
    b = tok0.shape[0]
    toks_buf = jnp.full((b, num_new_tokens), eos_id, jnp.int32)
    lps_buf = jnp.zeros((b, num_new_tokens), jnp.float32)
    toks_buf = toks_buf.at[:, 0].set(tok0)
    lps_buf = lps_buf.at[:, 0].set(lp0)
    state = (jnp.int32(1), tok0, tok0 == eos_id, caches, toks_buf, lps_buf,
             moe0)

    def cond(s):
        i, _, done, *_ = s
        return jnp.logical_and(i < num_new_tokens, ~jnp.all(done))

    def wbody(s):
        i, tok, done, caches, tb, lb, moe = s
        key = keys[i] if rng is not None else None
        ntok, lp, caches, moe = step(tok, caches, prompt_len + i - 1, key,
                                     moe)
        ntok = jnp.where(done, eos_id, ntok)
        lp = jnp.where(done, 0.0, lp)
        tb = tb.at[:, i].set(ntok)
        lb = lb.at[:, i].set(lp)
        return (i + 1, ntok, done | (ntok == eos_id), caches, tb, lb, moe)

    _, _, _, caches, toks_buf, lps_buf, moe = jax.lax.while_loop(
        cond, wbody, state)
    is_eos = (toks_buf == eos_id).astype(jnp.int32)
    after_eos = (jnp.cumsum(is_eos, axis=1) - is_eos) > 0
    res = {"tokens": toks_buf, "logprobs": lps_buf, "caches": caches,
           "gen_mask": 1.0 - after_eos.astype(jnp.float32)}
    return {**res, "moe": moe} if moe else res


# ----------------------------------------------------------- bucketed jit

GEN_BUCKETS = (16, 32, 64, 128, 256, 512, 1024, 2048)


def bucket_len(n: int, buckets=GEN_BUCKETS) -> int:
    """Smallest bucket >= n; lengths beyond the largest bucket get their
    own exact-size program (never truncated or negative-padded)."""
    for b in buckets:
        if n <= b:
            return b
    return n


class BucketedGenerator:
    """Length-bucketed jit cache over :func:`generate`.

    Variable-length prompt batches (e.g. ``data/synth.PromptDataset`` with
    ``min_len < prompt_len``) retrigger XLA compilation on every new
    (prompt_len, gen_len) pair when jitted naively.  This wrapper left-pads
    prompts to the next prompt-length bucket (left, so the final prompt
    token stays adjacent to generation — same convention as
    ``launch/serve.BatchServer``), rounds ``num_new_tokens`` up to its
    bucket, and keeps one compiled program per (prompt_bucket, gen_bucket,
    sampled?) key.  Outputs are trimmed back to the requested length.
    """

    def __init__(self, cfg: ModelConfig, *, temperature: float = 1.0,
                 impl: str = "reference", fused: bool = True,
                 eos_id: int | None = None, pad_id: int = 0,
                 sampler: str = "cdf", top_k: int = 0, top_p: float = 1.0,
                 buckets=GEN_BUCKETS):
        if cfg.prefix_len and cfg.family != "encdec":
            # left-padding tokens would shift them out from under the
            # prefix_embeds splice (positions [0:prefix_len])
            raise ValueError("BucketedGenerator does not support prefix "
                             "(vlm) configs; pad prompts upstream instead")
        self.cfg, self.temperature, self.impl = cfg, temperature, impl
        self.fused, self.eos_id, self.pad_id = fused, eos_id, pad_id
        self.sampler, self.top_k, self.top_p = sampler, top_k, top_p
        self.buckets = buckets
        self._fns: dict = {}
        self.compiles = 0
        self.hits = 0

    def _fn(self, prompt_bucket: int, gen_bucket: int, sampled: bool):
        # The compiled fn closes over every mutable sampling attribute below,
        # so each of them must be part of the cache key — otherwise switching
        # e.g. top_k after construction silently reuses a stale program.
        key = (prompt_bucket, gen_bucket, sampled, self.sampler, self.top_k,
               self.top_p, self.eos_id, self.temperature, self.fused,
               self.impl)
        fn = self._fns.get(key)
        if fn is None:
            self.compiles += 1

            def run(p, b, k):
                return generate(p, self.cfg, b, num_new_tokens=gen_bucket,
                                rng=(k if sampled else None),
                                temperature=self.temperature, impl=self.impl,
                                fused=self.fused, eos_id=self.eos_id,
                                sampler=self.sampler, top_k=self.top_k,
                                top_p=self.top_p)

            fn = self._fns[key] = jax.jit(run)
        else:
            self.hits += 1
        return fn

    def __call__(self, params, batch, *, num_new_tokens: int, rng=None):
        toks = batch["tokens"]
        plen = toks.shape[1]
        pb = bucket_len(plen, self.buckets)
        gb = bucket_len(num_new_tokens, self.buckets)
        if pb != plen:
            pad = jnp.full((toks.shape[0], pb - plen), self.pad_id, toks.dtype)
            batch = dict(batch, tokens=jnp.concatenate([pad, toks], axis=1))
        out = self._fn(pb, gb, rng is not None)(
            params, batch, rng if rng is not None
            else jax.random.PRNGKey(0))
        trimmed = {k: (v[:, :num_new_tokens]
                       if k in ("tokens", "logprobs", "gen_mask") else v)
                   for k, v in out.items()}
        return trimmed

    def stats(self) -> dict:
        return {"compiles": self.compiles, "hits": self.hits,
                "programs": len(self._fns)}


# ----------------------------------------------------------------- specs

def input_specs(cfg: ModelConfig, seq_len: int, batch: int, kind: str):
    """ShapeDtypeStruct stand-ins for the dry-run (no allocation)."""
    i32 = jnp.int32
    tok = jax.ShapeDtypeStruct((batch, seq_len), i32)
    f = jnp.dtype(cfg.dtype)
    specs = {"tokens": tok}
    if kind in ("train", "prefill"):
        if cfg.family == "encdec":
            specs["frames"] = jax.ShapeDtypeStruct(
                (batch, cfg.prefix_len, cfg.d_model), f)
        elif cfg.prefix_len:
            specs["prefix_embeds"] = jax.ShapeDtypeStruct(
                (batch, cfg.prefix_len, cfg.d_model), f)
    if kind == "train":
        specs["labels"] = tok
        specs["mask"] = jax.ShapeDtypeStruct((batch, seq_len), jnp.float32)
    return specs


def synth_batch(rng, cfg: ModelConfig, seq_len: int, batch: int, kind="train"):
    """Materialized synthetic batch matching input_specs (tests/examples)."""
    ks = jax.random.split(rng, 3)
    out = {"tokens": jax.random.randint(ks[0], (batch, seq_len), 0,
                                        cfg.vocab_size, jnp.int32)}
    if cfg.family == "encdec":
        out["frames"] = jax.random.normal(
            ks[1], (batch, cfg.prefix_len, cfg.d_model), jnp.dtype(cfg.dtype))
    elif cfg.prefix_len:
        out["prefix_embeds"] = jax.random.normal(
            ks[1], (batch, cfg.prefix_len, cfg.d_model), jnp.dtype(cfg.dtype))
    if kind == "train":
        out["labels"] = jax.random.randint(ks[2], (batch, seq_len), 0,
                                           cfg.vocab_size, jnp.int32)
        mask = jnp.ones((batch, seq_len), jnp.float32)
        if cfg.prefix_len and cfg.family != "encdec":
            mask = mask.at[:, :cfg.prefix_len].set(0.0)
        out["mask"] = mask
    return out
