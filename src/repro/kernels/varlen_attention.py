"""Varlen (packed ``cu_seqlens``) flash attention as a Pallas TPU kernel.

The packed training forward concatenates B ragged sequences on one token
axis; this kernel reuses the online-softmax core of ``flash_attention``
(running max / denom / accumulator in VMEM scratch, KV innermost on the
Mosaic grid) and replaces the rectangular causal mask with the
*block-diagonal* varlen mask: token i attends token j iff both belong to
the same sequence (and j <= i when causal).

``cu_seqlens`` rides in as **scalar prefetch** (the same pattern as the
grouped-expert GEMM's metadata): per-position segment ids are derived
inside the kernel by counting sequence starts at or before each position,
and four precomputed per-tile segment-range arrays
(first/last segment of every q/k tile) drive block-level skipping — a
(q-tile, k-tile) pair whose segment ranges don't overlap issues no
compute, which makes the whole kernel O(sum len_i^2 / block^2) tiles
instead of O((sum len_i)^2 / block^2): the packed analogue of the causal
block skip.

Phantom tokens beyond ``cu_seqlens[-1]`` (bucket padding) count as one
extra segment: they attend only themselves, so their rows stay finite and
the consumer's loss masks discard them.  Tier parity with
``ref.mha_varlen_ref`` is asserted for the valid region in
tests/test_packed.py; the parity contract is documented in ROADMAP.md.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import (
    LANES, NEG_INF, scores, softmax_finalize, softmax_init, softmax_update)


def _kernel(cu_ref, qlo_ref, qhi_ref, klo_ref, khi_ref, q_ref, k_ref, v_ref,
            o_ref, m_scr, l_scr, acc_scr, *, scale, block, n_k, n_seq,
            causal, window):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        softmax_init(m_scr, l_scr, acc_scr)

    q_start = iq * block
    k_start = ik * block

    # block-level skip: segment ranges must overlap (block-diagonal mask),
    # and under causality the k tile must not be entirely after the q tile
    live = jnp.logical_and(klo_ref[ik] <= qhi_ref[iq],
                           khi_ref[ik] >= qlo_ref[iq])
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + block - 1)
    if window is not None:
        live = jnp.logical_and(
            live, q_start <= k_start + block - 1 + window - 1)

    @pl.when(live)
    def _compute():
        s = scores(q_ref[0], k_ref[0], scale)  # (block, block)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)

        def seg_of(pos):
            # segment id = #sequence starts at or before pos; positions at
            # or beyond cu[-1] (phantom/pad) land in segment n_seq
            def body(sq, acc):
                return acc + (pos >= cu_ref[sq]).astype(jnp.int32)
            return jax.lax.fori_loop(1, n_seq + 1, body,
                                     jnp.zeros(pos.shape, jnp.int32))

        mask = seg_of(qpos) == seg_of(kpos)  # (block, block) block-diagonal
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        softmax_update(jnp.where(mask, s, NEG_INF),
                       v_ref[0].astype(jnp.float32), m_scr, l_scr, acc_scr)

    @pl.when(ik == n_k - 1)
    def _finalize():
        o_ref[0] = softmax_finalize(l_scr, acc_scr).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block",
                                             "interpret"))
def flash_mha_varlen(q, k, v, cu_seqlens, *, causal=True, window=None,
                     block=128, interpret=False):
    """q: (T, Hq, D); k/v: (T, Hkv, D); cu_seqlens: (B+1,) int32.
    Returns (T, Hq, D).  Rows at or beyond cu_seqlens[-1] are
    unspecified-but-finite (phantom segment)."""
    t, hq, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    n_seq = cu_seqlens.shape[0] - 1
    block = min(block, -(-t // 8) * 8)
    pad = (-t) % block
    # heads in front of tokens: (H, T, D) blocks are (block x head_dim)
    qt, kt, vt = (jnp.swapaxes(x, 0, 1) for x in (q, k, v))
    if pad:
        qt, kt, vt = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                      for x in (qt, kt, vt))
    t_pad = t + pad
    n = t_pad // block

    # per-tile segment ranges for the block-level skip (host-side jnp;
    # they ride in as scalar prefetch alongside cu_seqlens itself)
    cu = cu_seqlens.astype(jnp.int32)
    starts = jnp.arange(n, dtype=jnp.int32) * block
    ends = starts + block - 1
    seg_lo = jnp.searchsorted(cu[1:], starts, side="right").astype(jnp.int32)
    seg_hi = jnp.searchsorted(cu[1:], ends, side="right").astype(jnp.int32)

    kernel = functools.partial(_kernel, scale=1.0 / (d ** 0.5), block=block,
                               n_k=n, n_seq=n_seq, causal=causal,
                               window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(hq, n, n),
        in_specs=[
            pl.BlockSpec((1, block, d), lambda h, iq, ik, *_: (h, iq, 0)),
            pl.BlockSpec((1, block, d),
                         lambda h, iq, ik, *_, g=g: (h // g, ik, 0)),
            pl.BlockSpec((1, block, d),
                         lambda h, iq, ik, *_, g=g: (h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block, d),
                               lambda h, iq, ik, *_: (h, iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((block, LANES), jnp.float32),  # running max
            pltpu.VMEM((block, LANES), jnp.float32),  # running denom
            pltpu.VMEM((block, d), jnp.float32),      # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((hq, t_pad, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(cu, seg_lo, seg_hi, seg_lo, seg_hi, qt, kt, vt)
    return jnp.swapaxes(out[:, :t], 0, 1)
