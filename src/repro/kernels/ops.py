"""Dispatch layer over the Pallas kernels and their jnp oracles.

``impl`` selects the execution path:
  - "reference":         pure-jnp oracle (CPU tests, dry-run lowering)
  - "pallas":            Mosaic TPU kernel (target hardware)
  - "pallas_interpret":  Pallas interpret mode (CPU validation of kernel bodies)

Models take ``impl`` from their runtime context so the same model code lowers
for TPU with kernels and compiles on CPU with references.  These functions are
meant to be called from inside an enclosing ``jax.jit``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref

# "stub" short-circuits attention (returns q): used by the dry-run's
# attention-traffic probe to isolate how much of a superblock's HBM bytes the
# naive reference attention costs (= what the Pallas flash kernel eliminates).
IMPLS = ("reference", "pallas", "pallas_interpret", "stub")


def _check(impl):
    if impl not in IMPLS:
        raise ValueError(f"impl={impl!r} not in {IMPLS}")


def mha(q, k, v, *, causal=True, window=None, q_positions=None,
        kv_positions=None, scale=None, impl="reference"):
    """q: (B, Sq, Hq, D); k: (B, Skv, Hkv, D); v: (B, Skv, Hkv, Dv).
    ``scale`` multiplies the scores (default D**-0.5).  Returns
    (B, Sq, Hq, Dv)."""
    _check(impl)
    if impl == "stub":
        return q + 0.0 * (k.sum() + v.sum())
    if impl == "reference":
        return ref.mha_ref(q, k, v, causal=causal, window=window,
                           q_positions=q_positions, kv_positions=kv_positions,
                           scale=scale)
    from repro.kernels import flash_attention
    dv = v.shape[-1]
    if dv != q.shape[-1]:  # the kernel takes one width: zero columns of v
        v = jnp.pad(v, ((0, 0),) * 3 + ((0, q.shape[-1] - dv),))
    out = flash_attention.flash_mha(
        q, k, v, causal=causal, window=window, q_positions=q_positions,
        kv_positions=kv_positions, scale=scale,
        interpret=(impl == "pallas_interpret"))
    return out[..., :dv] if dv != q.shape[-1] else out


def varlen_mha(q, k, v, cu_seqlens, *, causal=True, window=None,
               max_seqlen=None, impl="reference"):
    """Packed (cu_seqlens) varlen attention over one token axis.

    q: (T, Hq, D); k/v: (T, Hkv, D); cu_seqlens: (B+1,) int32.  Token i
    attends token j iff both lie in the same ``cu_seqlens`` segment (and
    j <= i when causal); phantom tokens at or beyond ``cu_seqlens[-1]``
    form their own segment (finite outputs, discarded by loss masks).
    ``max_seqlen`` (static) lets the reference restrict each query chunk
    to its key band — without it the oracle scans all T keys."""
    _check(impl)
    if impl == "stub":
        return q + 0.0 * (k.sum() + v.sum())
    if impl == "reference":
        return ref.mha_varlen_ref(q, k, v, cu_seqlens, causal=causal,
                                  window=window, max_seqlen=max_seqlen)
    from repro.kernels import varlen_attention
    return varlen_attention.flash_mha_varlen(
        q, k, v, cu_seqlens, causal=causal, window=window,
        interpret=(impl == "pallas_interpret"))


def decode_mha(q, k_cache, v_cache, *, cache_len, window=None, layer=None,
               impl="reference"):
    """Single-token attention over one layer's cache (B, C, Hkv, D), or over
    layer ``layer`` of a scan group's stacked cache (L, B, C, Hkv*D), which
    the kernel reads in place."""
    _check(impl)
    if impl == "reference":
        if layer is not None:
            k_cache, v_cache = (
                jax.lax.dynamic_index_in_dim(c, layer, 0, False).reshape(
                    *c.shape[1:3], -1, q.shape[-1])
                for c in (k_cache, v_cache))
        return ref.decode_mha_ref(q, k_cache, v_cache, cache_len=cache_len,
                                  window=window)
    from repro.kernels import decode_attention
    return decode_attention.flash_decode(
        q, k_cache, v_cache, cache_len=cache_len, layer=layer, window=window,
        interpret=(impl == "pallas_interpret"))


def paged_decode_mha(q, k_pool, v_pool, block_table, *, cache_len,
                     impl="reference"):
    """Single-token decode attention over a paged (block-pool) KV cache.

    q: (B, Hq, D); pools: (N, bs, Hkv, D); block_table: (B, M) int32 of
    physical block ids; cache_len: (B,).  See ``ref.paged_decode_mha_ref``
    for the layout contract.  Returns (B, Hq, D)."""
    _check(impl)
    if impl == "stub":
        return q + 0.0 * (k_pool.sum() + v_pool.sum())
    if impl == "reference":
        return ref.paged_decode_mha_ref(q, k_pool, v_pool, block_table,
                                        cache_len=cache_len)
    from repro.kernels import paged_decode_attention
    return paged_decode_attention.paged_flash_decode(
        q, k_pool, v_pool, block_table, cache_len=cache_len,
        interpret=(impl == "pallas_interpret"))


def paged_verify_mha(q, k_pool, v_pool, block_table, *, q_positions,
                     impl="reference"):
    """Multi-query (speculative verify-step) attention over a paged KV cache.

    q: (B, K, Hq, D) — the spec_k + 1 verify tokens, whose KV has already
    been written into the pool; q_positions: (B, K) their absolute
    positions.  Query j attends every logical position <= q_positions[b, j]
    so one prefill-shaped dispatch scores the whole draft window.  Returns
    (B, K, Hq, D).  See ``ref.paged_verify_mha_ref`` for the parity
    contract with the single-token decode path."""
    _check(impl)
    if impl == "stub":
        return q + 0.0 * (k_pool.sum() + v_pool.sum())
    if impl == "reference":
        return ref.paged_verify_mha_ref(q, k_pool, v_pool, block_table,
                                        q_positions=q_positions)
    # "pallas" / "pallas_interpret": gather the table's block rows (an XLA
    # gather — the pool is already in HBM-friendly blocks) and run the flash
    # kernel with explicit positions; causal masking over logical positions
    # hides every unwritten slot.
    b, m = block_table.shape
    _, bs, hkv, d = k_pool.shape
    k_cache = k_pool[block_table].reshape(b, m * bs, hkv, d)
    v_cache = v_pool[block_table].reshape(b, m * bs, hkv, d)
    kv_positions = jnp.broadcast_to(jnp.arange(m * bs)[None], (b, m * bs))
    return mha(q, k_cache, v_cache, causal=True, window=None,
               q_positions=q_positions, kv_positions=kv_positions,
               impl="pallas_interpret" if impl == "pallas_interpret"
               else "pallas")


def grouped_ffn(xs, group_sizes, w_gate, w_in, w_out, *, act="silu",
                impl="reference"):
    """Grouped gated expert FFN over expert-sorted rows (dropless MoE).

    xs: (N, D) rows sorted by expert; group_sizes: (E,) int32 rows per
    expert, summing to N (the ragged group offsets are its cumsum);
    w_gate/w_in: (E, D, F); w_out: (E, F, D).  Returns (N, D) float32 — all tiers
    accumulate in fp32 and the combine caller casts once at the end.  Row
    i's result depends only on row i and its expert's weights, so the same
    token produces the same value (to fp reduction-order tolerance) in any
    cohort (training forward, prefill, decode) — the property the dropless
    dispatch exists for."""
    _check(impl)
    if impl in ("reference", "stub"):
        return ref.grouped_ffn_ref(xs, group_sizes, w_gate, w_in, w_out,
                                   act=act)
    from repro.kernels import grouped_expert
    return grouped_expert.grouped_ffn(
        xs, group_sizes, w_gate, w_in, w_out, act=act,
        interpret=(impl == "pallas_interpret"))


NEG_INF = -2.0**30


def _cdf_chunk(v: int) -> int:
    """Largest power-of-two chunk <= 1024 that divides V (0 = no chunking)."""
    k = 1024
    while k > 1:
        if v % k == 0 and v >= 2 * k:
            return k
        k //= 2
    return 0


def _sample_cdf(scaled, key):
    """Two-level inverse-CDF sample from (already tempered/truncated)
    logits — one uniform per row.

    Avoids the full-vocab Gumbel field of ``jax.random.categorical`` (V
    random bits per row) and the O(V) cumsum of a flat CDF: pass 1 reduces
    exp-sums per chunk, the chunk CDF is tiny, and only the selected chunk
    gets an exact intra-chunk cumsum.  Total (B, V) traffic ~2 read passes,
    nothing vocab-sized written.  Returns (token, logsumexp(scaled))."""
    b, v = scaled.shape
    m = jnp.max(scaled, axis=-1, keepdims=True)
    k = _cdf_chunk(v)
    u01 = jax.random.uniform(key, (b, 1))
    if k == 0:  # odd vocab sizes: flat CDF
        c = jnp.cumsum(jnp.exp(scaled - m), axis=-1)
        z = c[:, -1:]
        tok = jnp.sum(c < u01 * z, axis=-1)
        return (jnp.minimum(tok, v - 1).astype(jnp.int32),
                m[:, 0] + jnp.log(z[:, 0]))
    lgc = scaled.reshape(b, v // k, k)
    chunk = jnp.sum(jnp.exp(lgc - m[:, :, None]), axis=-1)  # (B, V/k)
    cchunk = jnp.cumsum(chunk, axis=-1)
    z = cchunk[:, -1:]
    u = u01 * z
    ci = jnp.minimum(jnp.sum(cchunk < u, axis=-1), v // k - 1)
    base = jnp.where(ci > 0,
                     jnp.take_along_axis(
                         cchunk, jnp.maximum(ci - 1, 0)[:, None], axis=-1)[:, 0],
                     0.0)
    sel = jnp.take_along_axis(lgc, ci[:, None, None], axis=1)[:, 0]  # (B, k)
    cin = jnp.cumsum(jnp.exp(sel - m), axis=-1)
    off = jnp.minimum(jnp.sum(base[:, None] + cin < u, axis=-1), k - 1)
    tok = (ci * k + off).astype(jnp.int32)
    return tok, m[:, 0] + jnp.log(z[:, 0])


def _truncate_logits(scaled, top_k: int, top_p: float):
    """Mask (tempered) logits outside the top-k / nucleus-top-p set.

    Masked entries go to NEG_INF, so the downstream CDF/Gumbel draw is the
    renormalized distribution over the kept set — no (B, V) probability
    array is written, only a masked copy of the logits the sampler was
    going to read anyway.  Top-p always keeps the most likely token; ties
    at the cutoff are kept (superset)."""
    v = scaled.shape[-1]
    if top_k and top_k < v:
        kth = jax.lax.top_k(scaled, top_k)[0][:, -1:]
        scaled = jnp.where(scaled < kth, NEG_INF, scaled)
    if top_p < 1.0:
        srt = jnp.sort(scaled, axis=-1)[:, ::-1]  # descending
        e = jnp.exp(srt - srt[:, :1])
        z = jnp.sum(e, axis=-1, keepdims=True)
        cdf_excl = (jnp.cumsum(e, axis=-1) - e) / z  # mass strictly above
        cnt = jnp.sum(cdf_excl < top_p, axis=-1, keepdims=True)  # >= 1
        thr = jnp.take_along_axis(srt, cnt - 1, axis=-1)
        scaled = jnp.where(scaled < thr, NEG_INF, scaled)
    return scaled


# lint: allow(impl-dispatch) -- all tiers share the jnp body (see docstring)
def sample_logits(logits, key=None, *, temperature: float = 1.0,
                  sampler: str = "cdf", top_k: int = 0, top_p: float = 1.0,
                  impl="reference"):
    """Fused sampling + logprob extraction from decode logits.

    logits: (B, V) or (B, K, V) — the 3-D form scores K positions per
    dispatch (the speculative verify step's k+1 distributions) by folding K
    into the row axis; one ``key`` covers all positions.  Returns (token
    (B,)/(B, K) int32, logprob (B,)/(B, K) f32) where the logprob is under
    the *untempered, untruncated* distribution (PPO convention — the scorer
    sees the full softmax).  The fusion never materializes a (B, V)
    ``log_softmax``; greedy when ``key`` is None.

    ``top_k`` (0 = off) and ``top_p`` (1.0 = off) truncate the *sampling*
    distribution: logits outside the kept set are masked to NEG_INF before
    the draw (mask-then-renormalize — the CDF/Gumbel pass renormalizes
    implicitly), so truncated sampling stays on the no-(B, V)-
    materialization fast path.  Greedy decoding ignores truncation (the
    argmax is always kept).

    ``sampler`` picks the stochastic path:
      - "cdf" (default): two-level inverse-CDF — one uniform per row, ~2
        read passes over the logits.  The fast path; draws differ from
        "gumbel" for the same key (both are exact samples).
      - "gumbel": ``jax.random.categorical`` — bit-identical to the
        pre-fusion decode loop, at the cost of a (B, V) Gumbel field.

    All tiers share the jnp body — these are V-reductions XLA fuses into
    the surrounding decode step on every backend, so the "pallas" tiers
    dispatch here rather than to a dedicated kernel."""
    _check(impl)
    if sampler not in ("cdf", "gumbel"):
        raise ValueError(f"sampler={sampler!r} not in ('cdf', 'gumbel')")
    if top_k < 0 or not 0.0 < top_p <= 1.0:
        raise ValueError(f"bad truncation top_k={top_k} top_p={top_p}")
    lg = logits.astype(jnp.float32)
    lead = lg.shape[:-1]
    if lg.ndim == 3:  # (B, K, V): score K positions in one pass
        lg = lg.reshape(-1, lg.shape[-1])
    truncated = bool(top_k and top_k < lg.shape[-1]) or top_p < 1.0
    lse = None
    if key is None:
        tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    else:
        scaled = lg if temperature == 1.0 else lg / max(temperature, 1e-6)
        if truncated:
            scaled = _truncate_logits(scaled, top_k, top_p)
        if sampler == "cdf":
            tok, lse_scaled = _sample_cdf(scaled, key)
            if temperature == 1.0 and not truncated:
                lse = lse_scaled  # reuse the sampler's partition function
        else:
            tok = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    if lse is None:
        lse = jax.nn.logsumexp(lg, axis=-1)
    lp = jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0] - lse
    return tok.reshape(lead), lp.reshape(lead)


# lint: allow(impl-dispatch) -- all tiers share the jnp body (see docstring)
def spec_verify(logits, draft_tokens, draft_logits, key=None, *,
                temperature: float = 1.0, top_k: int = 0, top_p: float = 1.0,
                impl="reference"):
    """Batched rejection sampling for speculative decoding.

    logits: (B, K+1, V) target logits at the verify positions (position i
    is the distribution *after* consuming token i of the verify window —
    i < K scores draft token i, position K is the bonus distribution);
    draft_tokens: (B, K) the draft's proposals; draft_logits: (B, K, V) the
    draft logits they were sampled from.  Returns

        accept_len (B,) int32  — leading draft tokens accepted, in [0, K]
        token      (B,) int32  — the committed correction/bonus token
        token_lp   (B,) f32    — its full-distribution target logprob
        draft_lps  (B, K) f32  — full-distribution target logprob of every
                                 draft token (rows [:accept_len] are the
                                 committed prefix's PPO logprobs)

    Sampled mode (``key`` given): draft token i is accepted with
    probability min(1, p(x_i)/q(x_i)) where p/q are the *sampling*
    distributions (temperature + top_k/top_p applied to both); the first
    rejection resamples from the normalized residual max(0, p - q), and a
    clean sweep samples the bonus position from p directly (residual with
    q = 0).  The committed-sequence distribution is exactly the target's —
    the rejection-sampling invariant.  Greedy mode (``key`` None): accept
    while the draft token equals the target argmax, correct with the
    argmax — bit-identical to greedy one-token decoding.

    Returned logprobs are always under the untempered, untruncated target
    distribution (PPO convention).  Nothing (B, K, V)-shaped beyond the
    input logits is materialized: scoring uses V-reductions, and only the
    single rejected position's (B, V) probability rows are formed for the
    residual draw.  All tiers share the jnp body (V-reductions XLA fuses
    into the verify step on every backend)."""
    _check(impl)
    if top_k < 0 or not 0.0 < top_p <= 1.0:
        raise ValueError(f"bad truncation top_k={top_k} top_p={top_p}")
    b, k1, v = logits.shape
    k = k1 - 1
    if k < 1 or draft_tokens.shape != (b, k) or draft_logits.shape != (b, k, v):
        raise ValueError(f"shape mismatch: logits {logits.shape}, "
                         f"draft_tokens {draft_tokens.shape}, "
                         f"draft_logits {draft_logits.shape}")
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)  # (B, K+1)
    draft_lps = jnp.take_along_axis(
        lg[:, :k], draft_tokens[:, :, None], axis=-1)[..., 0] - lse[:, :k]

    if key is None:
        tgt = jnp.argmax(lg, axis=-1).astype(jnp.int32)  # (B, K+1)
        ok = draft_tokens == tgt[:, :k]
        accept_len = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=-1),
                             axis=-1).astype(jnp.int32)
        token = jnp.take_along_axis(tgt, accept_len[:, None], axis=-1)[:, 0]
    else:
        qg = draft_logits.astype(jnp.float32)

        def scaled(x):
            s = x if temperature == 1.0 else x / max(temperature, 1e-6)
            if bool(top_k and top_k < v) or top_p < 1.0:
                flat = _truncate_logits(s.reshape(-1, v), top_k, top_p)
                s = flat.reshape(s.shape)
            return s

        pt, qt = scaled(lg), scaled(qg)
        lp_p = (jnp.take_along_axis(pt[:, :k], draft_tokens[:, :, None],
                                    axis=-1)[..., 0]
                - jax.nn.logsumexp(pt[:, :k], axis=-1))
        lp_q = (jnp.take_along_axis(qt, draft_tokens[:, :, None],
                                    axis=-1)[..., 0]
                - jax.nn.logsumexp(qt, axis=-1))
        ku, kr = jax.random.split(key)
        u = jax.random.uniform(ku, (b, k))
        ok = jnp.log(jnp.maximum(u, 1e-38)) < lp_p - lp_q
        accept_len = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=-1),
                             axis=-1).astype(jnp.int32)
        r = accept_len[:, None, None]
        p_probs = jax.nn.softmax(
            jnp.take_along_axis(pt, r, axis=1)[:, 0], axis=-1)  # (B, V)
        q_probs = jax.nn.softmax(
            jnp.take_along_axis(qt, jnp.minimum(r, k - 1), axis=1)[:, 0],
            axis=-1)
        q_probs = jnp.where((accept_len < k)[:, None], q_probs, 0.0)
        resid = jnp.maximum(p_probs - q_probs, 0.0)
        # fp guard: if p == q to rounding the residual mass underflows —
        # fall back to the target distribution (the exact-limit behavior)
        mass = jnp.sum(resid, axis=-1, keepdims=True)
        resid = jnp.where(mass > 0.0, resid, p_probs)
        token, _ = _sample_cdf(
            jnp.where(resid > 0.0, jnp.log(jnp.maximum(resid, 1e-38)),
                      NEG_INF), kr)

    lg_r = jnp.take_along_axis(lg, accept_len[:, None, None], axis=1)[:, 0]
    lse_r = jnp.take_along_axis(lse, accept_len[:, None], axis=1)[:, 0]
    token_lp = jnp.take_along_axis(lg_r, token[:, None], axis=-1)[:, 0] - lse_r
    return accept_len, token, token_lp, draft_lps


def ssd(x, dt, a_log, b_mat, c_mat, d_vec, *, chunk, init_state=None,
        return_state=False, impl="reference"):
    _check(impl)
    if impl == "reference":
        return ref.ssd_ref(x, dt, a_log, b_mat, c_mat, d_vec, chunk=chunk,
                           init_state=init_state, return_state=return_state)
    from repro.kernels import ssd_scan
    return ssd_scan.ssd_pallas(
        x, dt, a_log, b_mat, c_mat, d_vec, chunk=chunk, init_state=init_state,
        return_state=return_state, interpret=(impl == "pallas_interpret"))


# lint: allow(impl-dispatch) -- single-token O(H*N) elementwise recurrence with no kernel tier; the reference IS the implementation
def ssd_decode(x, dt, a_log, b_vec, c_vec, d_vec, state):
    return ref.ssd_decode_ref(x, dt, a_log, b_vec, c_vec, d_vec, state)


def rglru_scan(a, bx, init_state=None, *, impl="reference"):
    _check(impl)
    if impl == "reference":
        return ref.rglru_scan_ref(a, bx, init_state)
    from repro.kernels import rglru_scan as krn
    return krn.rglru_pallas(a, bx, init_state,
                            interpret=(impl == "pallas_interpret"))
