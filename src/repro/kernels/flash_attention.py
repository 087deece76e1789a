"""Flash attention (GQA, causal / sliding-window) as a Pallas TPU kernel.

TPU-native adaptation (see DESIGN.md §2): online-softmax accumulation over KV
blocks mapped onto the Mosaic grid — the KV dimension is the innermost
("arbitrary") grid axis carrying running (m, l, acc) in VMEM scratch.

Layout: the wrapper moves heads in front of the sequence, (B, H, S, D), so
every block is (block x head_dim) with the sequence block on the sublane
axis and head_dim spanning the whole lane axis — the (8, 128) tiling rule
holds at any head_dim and head count (qwen2-0.5b: 14 query heads, 2 KV
heads, head_dim 64).

Masking uses explicit positions (arange by default): query positions ride
in as a (block_q, 1) column and key positions as a (1, block_k) row.
Per-tile position bounds, computed in the wrapper, arrive by scalar
prefetch and drive the block-level skip: a tile pair with no causal /
in-window pair issues no compute, which keeps causal and sliding-window
attention O(S·W) rather than O(S²) for ordered positions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
NEG_INF = -2.0**30
PAD_POS = 2**30  # position given to padded keys (causal-masked everywhere)


def softmax_init(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def softmax_update(s, v, m_scr, l_scr, acc_scr):
    """Fold one (rows, block_k) tile of masked scores ``s`` and its values
    ``v`` (block_k, width) into the running (max, denom, acc) carry.  The
    max/denom scratch is (rows, LANES) with the value broadcast on lanes."""
    m_prev = m_scr[:, 0:1]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)
    l_cur = l_scr[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_cur, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_cur, l_scr.shape)


def softmax_finalize(l_scr, acc_scr):
    l = l_scr[:, 0:1]
    return acc_scr[...] / jnp.where(l == 0.0, 1.0, l)  # all-masked -> zeros


def scores(q, k, scale):
    """(rows, d) x (cols, d) -> (rows, cols) fp32 scores."""
    return jax.lax.dot_general(
        q.astype(jnp.float32), k.astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale


def _kernel(qlo_ref, qhi_ref, klo_ref, khi_ref, qpos_ref, kpos_ref, q_ref,
            k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, scale, block_k,
            n_q, n_k, causal, window, seq_k):
    bb = pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        softmax_init(m_scr, l_scr, acc_scr)

    # block-level skip from the tiles' position bounds
    qt, kt = bb * n_q + iq, bb * n_k + ik
    live = True
    if causal:
        live = jnp.asarray(qhi_ref[qt] >= klo_ref[kt])
    if window is not None:
        live = jnp.logical_and(live, qlo_ref[qt] - khi_ref[kt] < window)

    @pl.when(live)
    def _compute():
        s = scores(q_ref[0, 0], k_ref[0, 0], scale)  # (bq, bk)
        qpos = qpos_ref[0]  # (bq, 1)
        kpos = kpos_ref[0]  # (1, bk)
        kidx = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kidx < seq_k
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        softmax_update(jnp.where(mask, s, NEG_INF),
                       v_ref[0, 0].astype(jnp.float32), m_scr, l_scr, acc_scr)

    @pl.when(ik == n_k - 1)
    def _finalize():
        o_ref[0, 0] = softmax_finalize(l_scr, acc_scr).astype(o_ref.dtype)


def _tile_bounds(pos, block):
    """Per-tile (min, max) of a (B, S) position array, flattened (B*n,)."""
    b, s = pos.shape
    t = pos.reshape(b, s // block, block)
    return (jnp.min(t, axis=-1).reshape(-1).astype(jnp.int32),
            jnp.max(t, axis=-1).reshape(-1).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret"))
def flash_mha(q, k, v, *, causal=True, window=None, q_positions=None,
              kv_positions=None, block_q=128, block_k=128, interpret=False):
    """q: (B, Sq, Hq, D); k/v: (B, Skv, Hkv, D).  Returns (B, Sq, Hq, D).

    ``q_positions`` (B|1, Sq) / ``kv_positions`` (B|1, Skv): the logical
    positions the causal and window masks compare (default arange), as in
    ``ref.mha_ref``."""
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    if q_positions is None:
        q_positions = jnp.arange(sq)[None]
    if kv_positions is None:
        kv_positions = jnp.arange(skv)[None]
    qpos = jnp.broadcast_to(q_positions, (b, sq)).astype(jnp.int32)
    kpos = jnp.broadcast_to(kv_positions, (b, skv)).astype(jnp.int32)
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    pad_q = (-sq) % block_q
    pad_k = (-skv) % block_k
    qt = jnp.swapaxes(q, 1, 2)  # (B, Hq, Sq, D)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if pad_q:  # non-aligned shapes: pad (padded query rows are sliced off)
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        qpos = jnp.pad(qpos, ((0, 0), (0, pad_q)), mode="edge")
    if pad_k:  # padded keys are masked by index
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        kpos = jnp.pad(kpos, ((0, 0), (0, pad_k)), constant_values=PAD_POS)
    n_q = (sq + pad_q) // block_q
    n_k = (skv + pad_k) // block_k
    qlo, qhi = _tile_bounds(qpos, block_q)
    klo, khi = _tile_bounds(kpos, block_k)

    kernel = functools.partial(
        _kernel, scale=1.0 / (d ** 0.5), block_k=block_k, n_q=n_q, n_k=n_k,
        causal=causal, window=window, seq_k=skv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, 1), lambda bb, h, iq, ik, *_: (bb, iq, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bb, h, iq, ik, *_: (bb, 0, ik)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bb, h, iq, ik, *_: (bb, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, h, iq, ik, *_: (bb, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, h, iq, ik, *_: (bb, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bb, h, iq, ik, *_: (bb, h, iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, LANES), jnp.float32),  # running denom
            pltpu.VMEM((block_q, d), jnp.float32),      # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, sq + pad_q, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qlo, qhi, klo, khi, qpos[:, :, None], kpos[:, None, :], qt, kt, vt)
    return jnp.swapaxes(out[:, :, :sq], 1, 2)
