"""Speculative draft-and-verify rollout over the paged KV cache.

A small draft model proposes ``k`` tokens autoregressively; the target
verifies all ``k`` (+1 bonus sample) in one prefill-shaped dispatch
(:func:`model.paged_verify_step`); batched rejection sampling
(:func:`ops.spec_verify`) keeps the committed-token distribution *exactly*
the target's.  Accepted prefixes keep their appended KV blocks; a rejection
truncates the row's block list via ``BlockAllocator.truncate_to`` and the
stale pool slots are overwritten before they are ever attended.

Cache bookkeeping invariant: a row with committed length ``c`` has valid
target KV for positions ``0 .. c-2`` — the last committed token (position
``c-1``) is consumed, and its KV written, by the *next* verify dispatch.
The draft keeps the same convention over its own (statically-owned) block
pool, and each draft cycle ends with a consume-only catch-up step, so a
rejected proposal needs no rollback on either side: the next cycle's
writes land exactly on the stale positions.

The draft length adapts per cycle: :class:`SpecController` folds measured
accept rates into a per-cycle cost model (the calibrated ``CostModel``
supplies one via ``CostModel.spec_cycle_time_fn``) and picks the ``k``
minimizing expected time per committed token.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, ModelConfig
from repro.kernels import ops
from repro.models import model as MDL
from repro.models import paged_cache as PC
from repro.models import transformer as T


def spec_supported(cfg: ModelConfig) -> bool:
    """Speculative decoding needs rollback-free caches: attention layers
    (paged pools / ring buffers) only.  Recurrent mixers (RG-LRU / SSD)
    would need per-step state snapshots to undo rejected drafts."""
    if cfg.family == "encdec" or cfg.prefix_len:
        return False
    return all(s.kind == ATTN
               for specs, _ in T.groups_of(cfg) for s in specs)


def check_spec_pair(cfg: ModelConfig, draft_cfg: ModelConfig) -> None:
    """Raise ValueError unless (target, draft) can run draft-and-verify:
    both attention-only decoder models over one shared vocabulary."""
    for c, role in ((cfg, "target"), (draft_cfg, "draft")):
        if not spec_supported(c):
            raise ValueError(
                f"speculative decoding is attention-only (decoder-only, "
                f"prefix-free); {role} config {c.name!r} is not")
    if cfg.vocab_size != draft_cfg.vocab_size:
        raise ValueError(
            f"draft/target vocab mismatch: {draft_cfg.vocab_size} vs "
            f"{cfg.vocab_size}")


# ------------------------------------------------------------- controller

class SpecController:
    """Adaptive draft-length controller.

    Maintains an accept-rate EMA from measured verify outcomes and picks
    the draft length ``k`` minimizing expected cost per committed token,
    ``cycle_cost(k) / E[committed | a, k]`` with the truncated-geometric
    expectation ``E = (1 - a^(k+1)) / (1 - a)`` of rejection sampling.

    ``cycle_cost`` maps k to the cost of one draft+verify cycle.  Pass the
    calibrated estimator's ``CostModel.spec_cycle_time_fn(...)`` to drive
    the choice from measured profiles; the default is the analytic shape
    ``(k+1) * draft_cost + 1 + verify_marginal * k`` (k+1 draft dispatches
    — the last is the consume-only catch-up step — plus one verify whose
    marginal per-position cost is small when decode is bandwidth-bound).
    """

    def __init__(self, *, k_min: int = 1, k_max: int = 8, init_k: int = 4,
                 decay: float = 0.9, init_accept: float = 0.7,
                 cycle_cost=None, draft_cost: float = 0.3,
                 verify_marginal: float = 0.05):
        if not 1 <= k_min <= init_k <= k_max:
            raise ValueError(f"need 1 <= k_min <= init_k <= k_max, got "
                             f"{k_min}/{init_k}/{k_max}")
        self.k_min, self.k_max, self.decay = k_min, k_max, decay
        self.rate = float(init_accept)
        self.cycle_cost = cycle_cost or (
            lambda k: (k + 1) * draft_cost + 1.0 + verify_marginal * k)
        self.k = init_k
        self.history: list[tuple[float, int]] = []

    @staticmethod
    def expected_committed(accept_rate: float, k: int) -> float:
        """E[accepted prefix + 1] for i.i.d. per-token accept rate a."""
        a = min(max(float(accept_rate), 0.0), 0.999999)
        return (1.0 - a ** (k + 1)) / (1.0 - a)

    def _pick(self) -> int:
        return min(range(self.k_min, self.k_max + 1),
                   key=lambda k: self.cycle_cost(k)
                   / self.expected_committed(self.rate, k))

    def update(self, measured_rate: float) -> int:
        """Fold one cycle's measured accept rate in; returns the new k."""
        self.rate = (self.decay * self.rate
                     + (1.0 - self.decay) * float(measured_rate))
        self.k = self._pick()
        self.history.append((self.rate, self.k))
        return self.k


# ---------------------------------------------------- compiled dispatches
#
# Builders are lru_cached on (config, static sampling args) so repeated
# spec_generate / paged_generate calls — and the bench's timed loops —
# reuse the same jitted callables instead of retracing fresh closures.

@functools.lru_cache(maxsize=None)
def _admit_run(cfg: ModelConfig, prompt_len: int, sampled: bool,
               temperature: float, sampler: str, top_k: int, top_p: float,
               impl: str):
    """Jitted prompt admission: dense prefill -> paged insert -> first
    sampled token (the same fusion as the continuous-batching server)."""

    @jax.jit
    def run(params, batch, paged, table_rows, key):
        b = batch["tokens"].shape[0]
        last_h, dense, _ = MDL.prefill(params, cfg, batch, prompt_len,
                                       impl=impl)
        paged = PC.paged_insert(cfg, paged, dense, jnp.arange(b), table_rows,
                                prompt_len)
        logits0 = MDL.logits_of(params, cfg, last_h[:, None])[:, 0]
        tok0, lp0 = ops.sample_logits(
            logits0.astype(jnp.float32), key if sampled else None,
            temperature=temperature, sampler=sampler, top_k=top_k,
            top_p=top_p, impl=impl)
        return tok0, lp0, paged

    return run


@functools.lru_cache(maxsize=None)
def _decode_run(cfg: ModelConfig, sampled: bool, temperature: float,
                sampler: str, top_k: int, top_p: float, impl: str):
    """Jitted chunk of fused paged decode+sample steps (scan over the
    leading axis of ``keys``; re-specializes per chunk length)."""

    @jax.jit
    def run(params, caches, table, tok, pos, keys):
        def body(carry, key):
            tok, pos, caches = carry
            ntok, nlp, caches = MDL.paged_decode_and_sample_step(
                params, cfg, tok, caches, table, pos,
                key if sampled else None, temperature=temperature,
                sampler=sampler, top_k=top_k, top_p=top_p, impl=impl)
            return (ntok, pos + 1, caches), (ntok, nlp)
        (tok, _, caches), (toks, lps) = jax.lax.scan(
            body, (tok, pos, caches), keys)
        return tok, toks.T, lps.T, caches

    return run


@functools.lru_cache(maxsize=None)
def _draft_run(draft_cfg: ModelConfig, sampled: bool, temperature: float,
               sampler: str, top_k: int, top_p: float, impl: str):
    """Jitted draft cycle: scan of k+1 fused draft steps collecting the
    proposals and their full logits (re-specializes per k)."""

    @jax.jit
    def run(dparams, dcaches, d_table, tok, pos, keys):
        def body(carry, key):
            tok, pos, caches = carry
            ntok, logits, caches = MDL.paged_draft_step(
                dparams, draft_cfg, tok, caches, d_table, pos,
                key if sampled else None, temperature=temperature,
                sampler=sampler, top_k=top_k, top_p=top_p, impl=impl)
            return (ntok, pos + 1, caches), (ntok, logits)
        (_, _, dcaches), (toks, lgs) = jax.lax.scan(
            body, (tok, pos, dcaches), keys)
        # (k+1, B) proposals / (k+1, B, V) logits; the caller drops the
        # final consume-only step's outputs
        return toks.T, jnp.moveaxis(lgs, 0, 1), dcaches

    return run


@functools.lru_cache(maxsize=None)
def _verify_run(cfg: ModelConfig, sampled: bool, temperature: float,
                top_k: int, top_p: float, impl: str):
    """Jitted verify cycle: one prefill-shaped target dispatch over the
    spec window + batched rejection sampling."""

    @jax.jit
    def run(params, caches, table, tokens, positions, dtoks, dlgs, key):
        logits, caches = MDL.paged_verify_step(
            params, cfg, tokens, caches, table, positions, impl=impl)
        acc, tok, tok_lp, d_lps = ops.spec_verify(
            logits, dtoks, dlgs, key if sampled else None,
            temperature=temperature, top_k=top_k, top_p=top_p, impl=impl)
        return acc, tok, tok_lp, d_lps, caches

    return run


# ---------------------------------------------------------------- rollout

def _draft_table(batch: int, blocks_per_row: int) -> np.ndarray:
    """The draft owns its rows statically: row b gets the contiguous
    physical blocks [1 + b*M, 1 + (b+1)*M) (block 0 stays scratch), so it
    needs no allocator and no truncation — stale positions are masked."""
    return (1 + np.arange(batch)[:, None] * blocks_per_row
            + np.arange(blocks_per_row)[None, :]).astype(np.int32)


def paged_generate(params, cfg: ModelConfig, batch, *, num_new_tokens: int,
                   rng=None, temperature: float = 1.0, sampler: str = "cdf",
                   top_k: int = 0, top_p: float = 1.0, impl="reference",
                   block_size: int = 16, step_chunk: int = 1):
    """Non-speculative paged rollout: the baseline the speculative path is
    judged against.  One fused decode+sample dispatch per ``step_chunk``
    generated tokens (the continuous-batching server's per-step /
    sync_every granularity), with host-side block growth — all rows
    advance in lockstep, so this is :func:`model.generate` re-based onto
    the block pool.  Returns {"tokens": (B, T), "logprobs": (B, T)}."""
    b, p = batch["tokens"].shape
    bs = block_size
    max_len = p + num_new_tokens + step_chunk
    m = PC.needed_blocks(max_len, bs)
    n_blocks = b * m + PC.RESERVED_BLOCKS
    alloc = PC.BlockAllocator(n_blocks, bs)
    blocks = [alloc.alloc(PC.needed_blocks(p, bs)) for _ in range(b)]
    table = np.zeros((b, m), np.int32)
    nb0 = PC.needed_blocks(p, bs)
    for i, row in enumerate(blocks):
        table[i, :nb0] = row
    caches = PC.paged_cache_init(cfg, b, n_blocks, bs, max_len,
                                 jnp.dtype(cfg.dtype))
    sampled = rng is not None
    admit = _admit_run(cfg, p, sampled, temperature, sampler, top_k, top_p,
                       impl)
    step = _decode_run(cfg, sampled, temperature, sampler, top_k, top_p,
                       impl)
    n_keys = 1 + num_new_tokens
    keys = (jax.random.split(rng, n_keys) if sampled
            else jnp.zeros((n_keys, 2), jnp.uint32))
    tok, lp, caches = admit(params, batch, caches,
                            jnp.asarray(table[:, :nb0]), keys[0])
    toks_out = np.zeros((b, num_new_tokens), np.int32)
    lps_out = np.zeros((b, num_new_tokens), np.float32)
    toks_out[:, 0] = np.asarray(tok)
    lps_out[:, 0] = np.asarray(lp)
    g = 1  # tokens committed so far (the admission sample)
    while g < num_new_tokens:
        n = min(step_chunk, num_new_tokens - g)
        need = PC.needed_blocks(p + g + n, bs)
        for i in range(b):
            if need > len(blocks[i]):
                new = alloc.alloc(need - len(blocks[i]))
                table[i, len(blocks[i]):need] = new
                blocks[i].extend(new)
        pos = jnp.full((b,), p + g - 1, jnp.int32)
        tok, toks, lps, caches = step(params, caches, jnp.asarray(table),
                                      tok, pos, keys[g:g + n])
        toks_out[:, g:g + n] = np.asarray(toks)
        lps_out[:, g:g + n] = np.asarray(lps)
        g += n
    peak = alloc.peak
    for i in range(b):
        alloc.free(blocks[i])
    return {"tokens": jnp.asarray(toks_out), "logprobs": jnp.asarray(lps_out),
            "peak_blocks": peak}


def spec_generate(params, cfg: ModelConfig, draft_params,
                  draft_cfg: ModelConfig, batch, *, num_new_tokens: int,
                  spec_k: int = 4, rng=None, temperature: float = 1.0,
                  sampler: str = "cdf", top_k: int = 0, top_p: float = 1.0,
                  impl="reference", block_size: int = 16, controller=None):
    """Draft-and-verify rollout with PPO-exact logprobs.

    Per cycle: the draft proposes ``k`` tokens (k+1 fused decode dispatches
    — the last is the consume-only catch-up step that keeps the draft
    cache one token behind the commit point on every outcome); the target
    scores all k+1 positions in one :func:`model.paged_verify_step`
    dispatch; :func:`ops.spec_verify` accepts a prefix and resamples the
    first rejection from the residual.  Rows advance independently —
    per-row block lists grow before the verify and are truncated back to
    the committed length after it (``BlockAllocator.truncate_to``).

    Returned ``logprobs`` are the *target's* full-distribution logprobs of
    the committed tokens (equal to a teacher-forced forward recomputation
    to fp32 tolerance); with ``rng=None`` the committed tokens are
    bit-identical to greedy :func:`model.generate`.  ``stats`` reports
    accept rates, cycles, the per-cycle k trace, and the block pool's
    high-water mark.  When ``controller`` (a :class:`SpecController`) is
    given, ``k`` re-adapts every cycle from the measured accept rate and
    ``spec_k`` is ignored."""
    check_spec_pair(cfg, draft_cfg)
    if spec_k < 1:
        raise ValueError(f"spec_k must be >= 1, got {spec_k}")
    b, p = batch["tokens"].shape
    bs = block_size
    k_cap = controller.k_max if controller is not None else spec_k
    # a row can overshoot num_new_tokens by up to k commits before it
    # freezes, and frozen rows keep verifying at their pinned position
    max_len = p + num_new_tokens + 2 * k_cap + 1
    m = PC.needed_blocks(max_len, bs)
    n_blocks = b * m + PC.RESERVED_BLOCKS
    alloc = PC.BlockAllocator(n_blocks, bs)
    blocks = [alloc.alloc(PC.needed_blocks(p, bs)) for _ in range(b)]
    table = np.zeros((b, m), np.int32)
    nb0 = PC.needed_blocks(p, bs)
    for i, row in enumerate(blocks):
        table[i, :nb0] = row
    caches = PC.paged_cache_init(cfg, b, n_blocks, bs, max_len,
                                 jnp.dtype(cfg.dtype))
    md = PC.needed_blocks(max_len, bs)
    d_table = _draft_table(b, md)
    d_caches = PC.paged_cache_init(draft_cfg, b, b * md + 1, bs, max_len,
                                   jnp.dtype(draft_cfg.dtype))
    sampled = rng is not None
    key_box = [rng]

    def next_keys(n):
        if not sampled:
            return jnp.zeros((n, 2), jnp.uint32)
        key_box[0], sub = jax.random.split(key_box[0])
        return jax.random.split(sub, n)

    admit = _admit_run(cfg, p, sampled, temperature, sampler, top_k, top_p,
                       impl)
    d_admit = _admit_run(draft_cfg, p, sampled, temperature, sampler, top_k,
                         top_p, impl)
    draft = _draft_run(draft_cfg, sampled, temperature, sampler, top_k,
                       top_p, impl)
    verify = _verify_run(cfg, sampled, temperature, top_k, top_p, impl)

    tok0, lp0, caches = admit(params, batch, caches,
                              jnp.asarray(table[:, :nb0]), next_keys(1)[0])
    _, _, d_caches = d_admit(draft_params, batch, d_caches,
                             jnp.asarray(d_table[:, :nb0]), next_keys(1)[0])
    d_table_dev = jnp.asarray(d_table)

    buf = num_new_tokens + k_cap + 1
    toks_out = np.zeros((b, buf), np.int32)
    lps_out = np.zeros((b, buf), np.float32)
    toks_out[:, 0] = np.asarray(tok0)
    lps_out[:, 0] = np.asarray(lp0)
    gen = np.ones(b, np.int64)            # committed new tokens per row
    c = np.full(b, p + 1, np.int64)       # committed length (prompt + gen)
    cur_tok = np.asarray(tok0).copy()
    cycles, accepted_total, proposed_total = 0, 0, 0
    k_trace: list[int] = []

    while bool((gen < num_new_tokens).any()):
        k = controller.k if controller is not None else spec_k
        k_trace.append(k)
        for i in range(b):
            # a clean sweep commits k+1 tokens: the post-commit truncate_to
            # keeps blocks covering c+k+1, so grow to that (the last block
            # is written only by the NEXT cycle's verify, but keeping it
            # avoids free/realloc churn on every full accept)
            need = PC.needed_blocks(int(c[i]) + k + 1, bs)
            if need > len(blocks[i]):
                new = alloc.alloc(need - len(blocks[i]))
                table[i, len(blocks[i]):need] = new
                blocks[i].extend(new)
        pos0 = (c - 1).astype(np.int32)
        dtoks, dlgs, d_caches = draft(
            draft_params, d_caches, d_table_dev, jnp.asarray(cur_tok),
            jnp.asarray(pos0), next_keys(k + 1))
        dtoks = np.asarray(dtoks)[:, :k]          # drop the catch-up step
        dlgs_dev = jnp.asarray(np.asarray(dlgs)[:, :k])
        tokens = np.concatenate([cur_tok[:, None], dtoks], axis=1)
        positions = pos0[:, None] + np.arange(k + 1, dtype=np.int32)[None]
        acc, ytok, ylp, dlps, caches = verify(
            params, caches, jnp.asarray(table), jnp.asarray(tokens),
            jnp.asarray(positions), jnp.asarray(dtoks), dlgs_dev,
            next_keys(1)[0])
        acc = np.asarray(acc)
        ytok, ylp, dlps = np.asarray(ytok), np.asarray(ylp), np.asarray(dlps)
        cycles += 1
        cyc_acc = cyc_prop = 0
        for i in range(b):
            if gen[i] >= num_new_tokens:
                continue  # frozen row: state pinned, outputs ignored
            r = int(acc[i])
            cyc_acc += r
            cyc_prop += k
            g = int(gen[i])
            toks_out[i, g:g + r] = tokens[i, 1:1 + r]
            lps_out[i, g:g + r] = dlps[i, :r]
            toks_out[i, g + r] = ytok[i]
            lps_out[i, g + r] = ylp[i]
            gen[i] += r + 1
            c[i] += r + 1
            cur_tok[i] = ytok[i]
            blocks[i] = alloc.truncate_to(blocks[i], int(c[i]))
            table[i, len(blocks[i]):] = 0
        accepted_total += cyc_acc
        proposed_total += cyc_prop
        if controller is not None and cyc_prop:
            controller.update(cyc_acc / cyc_prop)

    accept_rate = accepted_total / max(proposed_total, 1)
    peak = alloc.peak
    for i in range(b):
        alloc.free(blocks[i])
    return {
        "tokens": jnp.asarray(toks_out[:, :num_new_tokens]),
        "logprobs": jnp.asarray(lps_out[:, :num_new_tokens]),
        "stats": {"cycles": cycles, "accept_rate": float(accept_rate),
                  "k_trace": k_trace, "peak_blocks": peak,
                  "accepted": int(accepted_total),
                  "proposed": int(proposed_total)},
    }
