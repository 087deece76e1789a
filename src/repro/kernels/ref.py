"""Pure-jnp reference oracles for every Pallas kernel in this package.

These are the ground truth used (a) by tests to validate the Pallas kernels in
interpret mode, (b) as the execution path on non-TPU backends (this container,
and the multi-pod dry-run, which only lowers/compiles).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -2.0**30  # large-but-finite; avoids NaN from all-masked rows


# ---------------------------------------------------------------------------
# Attention (GQA, causal / sliding-window / bidirectional)
# ---------------------------------------------------------------------------

def mha_ref(q, k, v, *, causal: bool = True, window: int | None = None,
            q_positions=None, kv_positions=None, logits_dtype=jnp.float32,
            q_chunk: int | None = 0, scale: float | None = None):
    """Multi-head attention with grouped KV heads.

    q: (B, Sq, Hq, D); k: (B, Skv, Hkv, D); v: (B, Skv, Hkv, Dv) with
    Hq % Hkv == 0.  ``scale`` multiplies the scores (default D**-0.5).
    ``window``: sliding-window size (keys with q_pos - k_pos >= window masked).
    Positions default to arange; pass explicitly for decode / ring caches.

    ``q_chunk``: statically unroll over query chunks so the score working set
    is (B, H, q_chunk, Skv) instead of (B, H, Sq, Skv) — exact math, bounded
    memory, and no extra ``while`` loop (keeps HLO cost accounting simple).
    0 = auto (chunk long sequences); None = never chunk.
    Returns (B, Sq, Hq, Dv) in q.dtype.
    """
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    if q_positions is None:
        q_positions = jnp.arange(sq)[None, :]
    if kv_positions is None:
        kv_positions = jnp.arange(skv)[None, :]

    if q_chunk == 0:
        q_chunk = 256
    if q_chunk and sq > q_chunk and sq % q_chunk == 0 and sq == skv:
        outs = []
        for i in range(sq // q_chunk):
            lo, hi = i * q_chunk, (i + 1) * q_chunk
            klo = 0
            if causal and kv_positions.shape[0] == 1:
                # keys after this chunk's last query are fully masked; with a
                # window, keys before (first query - window + 1) are too
                khi = hi
                if window is not None:
                    klo = max(0, lo - window + 1)
            else:
                khi = skv
            outs.append(mha_ref(
                q[:, lo:hi], k[:, klo:khi], v[:, klo:khi], causal=causal,
                window=window, q_positions=q_positions[:, lo:hi],
                kv_positions=kv_positions[:, klo:khi],
                logits_dtype=logits_dtype, q_chunk=None, scale=scale))
        return jnp.concatenate(outs, axis=1)

    qr = q.reshape(b, sq, hkv, g, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qr, k).astype(logits_dtype)
    if scale is None:
        logits = logits / jnp.sqrt(d).astype(logits_dtype)
    else:
        logits = logits * jnp.asarray(scale, logits_dtype)

    dq = q_positions[:, None, None, :, None]  # (b,1,1,sq,1)
    dk = kv_positions[:, None, None, None, :]  # (b,1,1,1,skv)
    mask = jnp.ones((b, 1, 1, sq, skv), dtype=bool)
    if causal:
        mask &= dk <= dq
    if window is not None:
        mask &= (dq - dk) < window
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out.reshape(b, sq, hq, v.shape[-1])


def _varlen_block(q, k, v, seg_q, seg_k, pos_q, pos_k, *, causal, window,
                  g: int):
    """One (Tq, Tk) tile of packed varlen attention.  q: (Tq, Hq, D);
    k/v: (Tk, Hkv, D); seg_*/pos_*: int32 segment ids / global positions.
    Tokens attend only within their own segment (block-diagonal mask)."""
    tq, hq, d = q.shape
    hkv = k.shape[1]
    qr = q.reshape(tq, hkv, g, d)
    logits = jnp.einsum("qhgd,khd->hgqk", qr, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(d).astype(jnp.float32)
    mask = seg_q[:, None] == seg_k[None, :]
    if causal:
        mask &= pos_k[None, :] <= pos_q[:, None]
    if window is not None:
        mask &= (pos_q[:, None] - pos_k[None, :]) < window
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("hgqk,khd->qhgd", probs.astype(v.dtype), v)
    return out.reshape(tq, hq, d)


def mha_varlen_ref(q, k, v, cu_seqlens, *, causal: bool = True,
                   window: int | None = None, max_seqlen: int | None = None,
                   q_chunk: int = 128):
    """Packed variable-length attention: the oracle for
    ``varlen_attention.flash_mha_varlen``.

    q: (T, Hq, D); k/v: (T, Hkv, D) — the B sequences concatenated on the
    token axis with offsets ``cu_seqlens`` ((B+1,) int32).  The mask is
    block-diagonal (a token only attends keys of its own sequence, causal
    within when ``causal``); phantom tokens beyond ``cu_seqlens[-1]`` form
    one extra segment of their own (outputs unspecified-but-finite).

    ``max_seqlen`` (static) bounds the longest sequence: with it and
    ``causal`` the computation runs banded — query chunks against the
    trailing ``max_seqlen``-wide key band — so cost is O(T·max_seqlen)
    instead of O(T²), the packed analogue of ``mha_ref``'s q-chunking.
    Changing the tokens of sequence j leaves sequence i's output
    bit-identical: cross-segment scores are hard-masked to NEG_INF before
    the softmax, contributing exactly 0.0 to the combine.
    """
    t, hq, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    cu = jnp.asarray(cu_seqlens)
    pos = jnp.arange(t)
    seg = jnp.searchsorted(cu[1:], pos, side="right").astype(jnp.int32)

    band = max_seqlen if (causal and max_seqlen is not None) else None
    if band is None or t <= q_chunk:
        return _varlen_block(q, k, v, seg, seg, pos, pos, causal=causal,
                             window=window, g=g)
    outs = []
    for i in range(-(-t // q_chunk)):
        lo, hi = i * q_chunk, min((i + 1) * q_chunk, t)
        # same-segment causal keys of queries [lo, hi) all lie in
        # [lo - band + 1, hi): a key more than band-1 behind its query is
        # in an earlier sequence (sequences are contiguous, len <= band)
        klo = max(0, lo - band + 1)
        outs.append(_varlen_block(
            q[lo:hi], k[klo:hi], v[klo:hi], seg[lo:hi], seg[klo:hi],
            pos[lo:hi], pos[klo:hi], causal=causal, window=window, g=g))
    return jnp.concatenate(outs, axis=0)


def decode_mha_ref(q, k_cache, v_cache, *, cache_len, window: int | None = None):
    """Single-token decode attention over a (ring or linear) KV cache.

    q: (B, Hq, D).  k_cache/v_cache: (B, C, Hkv, D) where C is the cache
    capacity.  ``cache_len``: (B,) number of tokens written so far (the new
    token's position).  For a ring cache (C == window) all slots are valid
    once cache_len >= C.  Returns (B, Hq, D).
    """
    b, c, hkv, d = k_cache.shape
    hq = q.shape[1]
    g = hq // hkv
    qr = q.reshape(b, hkv, g, d)
    logits = jnp.einsum("bhgd,bkhd->bhgk", qr, k_cache).astype(jnp.float32)
    logits = logits / jnp.sqrt(d).astype(jnp.float32)
    slots = jnp.arange(c)[None, :]  # (1, C)
    n = cache_len[:, None]  # (B, 1)
    valid = slots < jnp.minimum(n, c)
    if window is not None:
        # ring cache: every stored slot is within the window by construction
        valid = slots < jnp.minimum(n, min(c, window))
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", probs.astype(v_cache.dtype), v_cache)
    return out.reshape(b, hq, d)


def paged_decode_mha_ref(q, k_pool, v_pool, block_table, *, cache_len):
    """Single-token decode attention over a paged (block-pool) KV cache.

    q: (B, Hq, D).  k_pool/v_pool: (N, bs, Hkv, D) — a shared pool of N
    fixed-size blocks of bs tokens each.  ``block_table``: (B, M) int32
    physical block ids; logical position p of sequence b lives at
    ``pool[block_table[b, p // bs], p % bs]``.  ``cache_len``: (B,) tokens
    written so far (the new token's position + 1).  Unallocated table
    entries may point anywhere (conventionally block 0); they are masked
    because every position >= cache_len is masked.  Returns (B, Hq, D).
    """
    b, m = block_table.shape
    _, bs, hkv, d = k_pool.shape
    k_cache = k_pool[block_table].reshape(b, m * bs, hkv, d)
    v_cache = v_pool[block_table].reshape(b, m * bs, hkv, d)
    return decode_mha_ref(q, k_cache, v_cache, cache_len=cache_len)


def paged_verify_mha_ref(q, k_pool, v_pool, block_table, *, q_positions):
    """Multi-query (speculative verify-step) attention over a paged KV cache.

    q: (B, K, Hq, D) — the K = spec_k + 1 verify tokens of each row;
    ``q_positions``: (B, K) their absolute positions (consecutive per row).
    The KV of all K tokens has already been scattered into the pool, so
    query j attends every logical position <= q_positions[b, j].  The
    gather order and masked key set at each query position are identical to
    what :func:`paged_decode_mha_ref` sees for a single-token step at that
    position — the bit-parity requirement of the rejection-sampling
    invariant.  Returns (B, K, Hq, D).
    """
    b, m = block_table.shape
    _, bs, hkv, d = k_pool.shape
    k_cache = k_pool[block_table].reshape(b, m * bs, hkv, d)
    v_cache = v_pool[block_table].reshape(b, m * bs, hkv, d)
    kv_positions = jnp.broadcast_to(jnp.arange(m * bs)[None], (b, m * bs))
    return mha_ref(q, k_cache, v_cache, causal=True, window=None,
                   q_positions=q_positions, kv_positions=kv_positions,
                   q_chunk=None)


# ---------------------------------------------------------------------------
# Grouped (dropless MoE) expert FFN
# ---------------------------------------------------------------------------

ACTS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}


def expert_ids_of(group_sizes, n: int):
    """Per-row expert id from ragged group offsets: row i of the
    expert-sorted layout belongs to the first expert whose (inclusive)
    cumsum offset exceeds i.  group_sizes: (E,) int32; ids for rows beyond
    the total are clamped to the last expert (indexing safety only —
    ``grouped_ffn_ref`` zeroes those rows' outputs)."""
    ends = jnp.cumsum(group_sizes)
    eid = jnp.searchsorted(ends, jnp.arange(n), side="right")
    return jnp.minimum(eid, group_sizes.shape[0] - 1).astype(jnp.int32)


def row_tiles(n: int, block_rows: int) -> tuple[int, int]:
    """(bn, n_pad): the 8-aligned row-tile size (<= block_rows) and padded
    row count of the Pallas grouped expert kernel's unit schedule."""
    bn = min(block_rows, max(8, -(-n // 8) * 8))
    return bn, -(-n // bn) * bn


def group_metadata(group_sizes, n_pad: int, bn: int):
    """Per-work-unit dispatch metadata for the grouped expert GEMM (the
    Pallas kernel's scalar prefetch; all shapes static).

    Rows are tiled into ``bn``-row m-tiles; a tile straddling a group
    boundary is processed once per group it intersects, so the worst case
    is ``tiles_m + E - 1`` units.  Returns (unit_group, unit_tile, unit_lo,
    unit_hi, unit_first), each (tiles_m + E - 1,) int32.  Units beyond the
    real total (fewer straddles than worst case, empty experts) alias the
    last m-tile and the last nonempty expert with an empty [0, 0) row
    range, so consumers skip their compute entirely — and, in the Pallas
    kernel, their unchanged block indices issue no DMAs.
    """
    e = group_sizes.shape[0]
    tiles_m = n_pad // bn
    units = tiles_m + e - 1
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    t_start = starts // bn
    t_end = jnp.where(sizes > 0, (ends + bn - 1) // bn, t_start)
    tiles_pg = (t_end - t_start).astype(jnp.int32)  # 0 for empty experts
    cum = jnp.cumsum(tiles_pg)
    total = cum[-1]
    gids = jnp.arange(units, dtype=jnp.int32)
    ug = jnp.searchsorted(cum, gids, side="right").astype(jnp.int32)
    valid = gids < total
    ug = jnp.minimum(ug, e - 1)
    unit_base = cum[ug] - tiles_pg[ug]
    ut = jnp.where(valid, t_start[ug] + (gids - unit_base), tiles_m - 1)
    lo = jnp.where(valid, starts[ug], 0).astype(jnp.int32)
    hi = jnp.where(valid, ends[ug], 0).astype(jnp.int32)
    # padding units alias the last *nonempty* expert (and the last m-tile):
    # consecutive equal block indices mean the Pallas pipeline re-fetches
    # nothing for them, and their empty [0, 0) row range skips the compute
    last_ne = jnp.max(jnp.where(sizes > 0, jnp.arange(e, dtype=jnp.int32), -1))
    ug = jnp.where(valid, ug, jnp.maximum(last_ne, 0))
    prev = jnp.concatenate([jnp.full((1,), -1, ut.dtype), ut[:-1]])
    first = (ut != prev).astype(jnp.int32)
    return ug, ut.astype(jnp.int32), lo, hi, first


def grouped_ffn_ref(xs, group_sizes, w_gate, w_in, w_out, *, act="silu",
                    gather_limit: int = 1 << 22):
    """Grouped gated expert FFN over expert-sorted rows (dropless MoE).

    xs: (N, D) rows already sorted by expert; group_sizes: (E,) int32 rows
    per expert (ragged group offsets = its cumsum; must sum to N — the
    reference regimes zero any tail rows beyond the total, the Pallas tier
    leaves them undefined); w_gate/w_in: (E, D, F); w_out: (E, F, D).  Row
    i runs through expert ``expert_ids_of(...)[i]`` only — no capacity
    padding, no drops, and each row's result depends on nothing but that
    row and its expert's weights (cohort independence).  Computes in fp32
    and returns (N, D) float32; callers cast once.

    Two regimes (same per-row math, chosen by static shape):
      * small N x D x F (decode steps, CPU tests): per-row weight gather —
        exactly N rows of work, nothing expert-count-shaped.
      * large (training cohorts, dry-run lowering): ``lax.ragged_dot``, one
        grouped matmul per projection over the sorted rows (the gather
        would materialize N x D x F), differentiable with no per-group
        residuals.
    """
    n, d = xs.shape
    f32 = jnp.float32
    act_fn = ACTS[act]

    if n * d * w_gate.shape[-1] <= gather_limit:
        eid = expert_ids_of(group_sizes, n)
        x32 = xs.astype(f32)
        g = act_fn(jnp.einsum("nd,ndf->nf", x32, w_gate[eid].astype(f32)))
        h = g * jnp.einsum("nd,ndf->nf", x32, w_in[eid].astype(f32))
        y = jnp.einsum("nf,nfd->nd", h, w_out[eid].astype(f32))
        in_group = jnp.arange(n) < jnp.sum(group_sizes)
        return jnp.where(in_group[:, None], y, 0.0)

    gs = group_sizes.astype(jnp.int32)
    # ragged_dot leaves rows past the groups undefined (on the chip; zero
    # on the CPU): zero them going in, between the projections and coming
    # out, so that neither they nor their gradients reach a row in a group
    valid = (jnp.arange(n) < jnp.sum(gs))[:, None]
    x = jnp.where(valid, xs.astype(f32), 0.0)
    wg, wi, wo = (w.astype(f32) for w in (w_gate, w_in, w_out))
    g = act_fn(jax.lax.ragged_dot(x, wg, gs))
    h = jnp.where(valid, g * jax.lax.ragged_dot(x, wi, gs), 0.0)
    return jnp.where(valid, jax.lax.ragged_dot(h, wo, gs), 0.0)


# ---------------------------------------------------------------------------
# Mamba-2 SSD (state-space duality), chunked
# ---------------------------------------------------------------------------

def _segsum(x):
    """x: (..., L) -> (..., L, L) lower-triangular inclusive segment sums:
    out[i, j] = sum_{k=j+1..i} x[k] for i >= j, -inf above the diagonal."""
    L = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((L, L), dtype=bool), k=0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_ref(x, dt, a_log, b_mat, c_mat, d_vec, *, chunk: int, init_state=None,
            return_state: bool = False):
    """Chunked SSD forward (Mamba-2, ngroups=1).

    x: (B, S, H, P); dt: (B, S, H) (already softplus-ed, > 0);
    a_log: (H,) (A = -exp(a_log)); b_mat, c_mat: (B, S, N); d_vec: (H,).
    h_t = exp(dt_t * A) h_{t-1} + dt_t * B_t x_t^T ;  y_t = C_t . h_t + D x_t
    Returns y (B,S,H,P) and optionally the final state (B,H,P,N).
    """
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc, cl = s // chunk, chunk
    f32 = jnp.float32

    dA = (dt.astype(f32) * (-jnp.exp(a_log.astype(f32)))[None, None, :])  # (B,S,H) log-decay
    xr = (x.astype(f32) * dt.astype(f32)[..., None]).reshape(bsz, nc, cl, h, p)
    xo = x.astype(f32).reshape(bsz, nc, cl, h, p)
    dA = dA.reshape(bsz, nc, cl, h)
    br = b_mat.astype(f32).reshape(bsz, nc, cl, n)
    cr = c_mat.astype(f32).reshape(bsz, nc, cl, n)

    cums = jnp.cumsum(dA, axis=2)  # inclusive (B,NC,CL,H)
    # Intra-chunk (diagonal blocks)
    L = jnp.exp(_segsum(dA.transpose(0, 1, 3, 2)))  # (B,NC,H,CL,CL)
    scores = jnp.einsum("bcln,bcmn->bclm", cr, br)  # (B,NC,CL,CL)
    y_diag = jnp.einsum("bclm,bchlm,bcmhp->bclhp", scores, L, xr)

    # Per-chunk outgoing states
    decay_to_end = jnp.exp(cums[:, :, -1:, :] - cums)  # (B,NC,CL,H)
    s_local = jnp.einsum("bcln,bclh,bclhp->bchpn", br, decay_to_end, xr)

    # Inter-chunk recurrence over chunk states
    chunk_decay = jnp.exp(cums[:, :, -1, :])  # (B,NC,H)
    if init_state is None:
        init_state = jnp.zeros((bsz, h, p, n), f32)
    else:
        init_state = init_state.astype(f32)

    def step(carry, inp):
        dec, sl = inp  # (B,H), (B,H,P,N)
        new = carry * dec[..., None, None] + sl
        return new, carry  # emit the state PRIOR to this chunk

    final_state, s_prev = jax.lax.scan(
        step, init_state,
        (chunk_decay.transpose(1, 0, 2), s_local.transpose(1, 0, 2, 3, 4)))
    s_prev = s_prev.transpose(1, 0, 2, 3, 4)  # (B,NC,H,P,N)

    y_off = jnp.einsum("bcln,bchpn,bclh->bclhp", cr, s_prev, jnp.exp(cums))
    y = (y_diag + y_off).reshape(bsz, s, h, p)
    y = y + d_vec.astype(f32)[None, None, :, None] * x.astype(f32)
    y = y.astype(x.dtype)
    if return_state:
        return y, final_state
    return y


def ssd_decode_ref(x, dt, a_log, b_vec, c_vec, d_vec, state):
    """One decode step.  x: (B,H,P); dt: (B,H); b_vec,c_vec: (B,N);
    state: (B,H,P,N).  Returns (y, new_state)."""
    f32 = jnp.float32
    dA = jnp.exp(dt.astype(f32) * (-jnp.exp(a_log.astype(f32)))[None, :])  # (B,H)
    upd = jnp.einsum("bhp,bn->bhpn", x.astype(f32) * dt.astype(f32)[..., None],
                     b_vec.astype(f32))
    new_state = state * dA[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", new_state, c_vec.astype(f32))
    y = y + d_vec.astype(f32)[None, :, None] * x.astype(f32)
    return y.astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# RG-LRU (RecurrentGemma) linear recurrence
# ---------------------------------------------------------------------------

def rglru_scan_ref(a, bx, init_state=None):
    """h_t = a_t * h_{t-1} + bx_t, computed with an associative scan.

    a, bx: (B, S, W) with a in (0, 1].  Returns (h, final_state)."""
    f32 = jnp.float32
    a32, b32 = a.astype(f32), bx.astype(f32)
    if init_state is not None:
        b32 = b32.at[:, 0].add(a32[:, 0] * init_state.astype(f32))

    def combine(x, y):
        ax, bxx = x
        ay, byy = y
        return ax * ay, ay * bxx + byy

    ha, hb = jax.lax.associative_scan(combine, (a32, b32), axis=1)
    return hb.astype(bx.dtype), hb[:, -1]
