"""Flash-decode: single-token attention over a (ring or linear) KV cache as a
Pallas TPU kernel.

One grid instance handles one batch row and all of its heads.  The cache
keeps its model layout (B, C, Hkv, D), viewed as (B, C, Hkv*D) — a free
reshape — so a KV tile is (block_k x Hkv*D): the sequence block on the
sublane axis and every KV head together on the lane axis, which meets the
(8, 128) tiling at any head_dim.  The query is spread block-diagonally to
(Hq, Hkv*D) (query head h keeps its values in the lane slot of its KV head
and zeros elsewhere), so one (Hq x Hkv*D) . (Hkv*D x block_k) product
scores every head against its own KV head, and the PV product's diagonal
slots are the per-head outputs.  ``cache_len`` arrives by scalar prefetch
and masks unwritten slots — ring caches (window attention) are handled by
the same bound since every resident slot is in-window by construction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import (
    LANES, NEG_INF, scores, softmax_finalize, softmax_init, softmax_update)


def spread_heads(q, hkv):
    """(B, Hq, D) -> (B, Hq, Hkv*D), query head h's values in the lane slot
    of its KV head h // (Hq/Hkv), zeros in the other slots."""
    b, hq, d = q.shape
    g = hq // hkv
    eye = jnp.eye(hkv, dtype=q.dtype)[None, :, None, :, None]
    return (q.reshape(b, hkv, g, 1, d) * eye).reshape(b, hq, hkv * d)


def gather_heads(out, hkv):
    """Inverse of ``spread_heads`` on the kernel output: keep each query
    head's own KV-head slot.  (B, Hq, Hkv*D) -> (B, Hq, D)."""
    b, hq, w = out.shape
    d = w // hkv
    o = out.reshape(b, hkv, hq // hkv, hkv, d)
    return jnp.moveaxis(jnp.diagonal(o, axis1=1, axis2=3), -1, 1).reshape(
        b, hq, d)


def online_softmax_step(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                        scale, limit, k_start, step, n_steps):
    """One KV-tile step of the shared online-softmax decode body.

    ``q_ref``: (1, Hq, W) spread query; ``k_ref``/``v_ref``: (1, bk, W)
    tiles; ``step``/``n_steps``: position in the innermost ("arbitrary")
    grid axis; ``k_start``: logical position of this tile's first key;
    ``limit``: number of valid keys for this row.  Shared by the contiguous
    (``flash_decode``) and block-table-paged (``paged_flash_decode``)
    kernels — only how (limit, tile) are derived differs between them."""
    @pl.when(step == 0)
    def _init():
        softmax_init(m_scr, l_scr, acc_scr)

    @pl.when(k_start < limit)
    def _compute():
        s = scores(q_ref[0], k_ref[0], scale)  # (Hq, bk)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        softmax_update(jnp.where(kpos < limit, s, NEG_INF),
                       v_ref[0].astype(jnp.float32), m_scr, l_scr, acc_scr)

    @pl.when(step == n_steps - 1)
    def _finalize():
        o_ref[0] = softmax_finalize(l_scr, acc_scr).astype(o_ref.dtype)


def decode_call(kernel, *, name, grid, num_scalar_prefetch, q_index,
                kv_index, b, hq, width, block_k, dtype, interpret):
    """The ``pallas_call`` shared by the contiguous and paged decode
    kernels: a (b, kv-tile) grid over (1, Hq, W) queries/outputs and
    (1, block_k, W) KV tiles, with (max, denom, acc) scratch.  ``name``
    names the kernel in the compiled program and in device traces."""
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, hq, width), q_index),
            pl.BlockSpec((1, block_k, width), kv_index),
            pl.BlockSpec((1, block_k, width), kv_index),
        ],
        out_specs=pl.BlockSpec((1, hq, width), q_index),
        scratch_shapes=[
            pltpu.VMEM((hq, LANES), jnp.float32),
            pltpu.VMEM((hq, LANES), jnp.float32),
            pltpu.VMEM((hq, width), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, width), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, block_k, n_k, cap):
    bb = pl.program_id(0)
    ik = pl.program_id(1)
    online_softmax_step(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                        scale=scale, limit=jnp.minimum(len_ref[bb], cap),
                        k_start=ik * block_k, step=ik, n_steps=n_k)


@functools.partial(jax.jit, static_argnames=("window", "block_k", "interpret"))
def flash_decode(q, k_cache, v_cache, *, cache_len, window=None, block_k=256,
                 interpret=False):
    """q: (B, Hq, D); caches: (B, C, Hkv, D); cache_len: (B,) int32.
    Returns (B, Hq, D)."""
    b, hq, d = q.shape
    _, cap, hkv, _ = k_cache.shape
    w = hkv * d
    block_k = min(block_k, cap)
    pad = (-cap) % block_k
    kw = k_cache.reshape(b, cap, w)
    vw = v_cache.reshape(b, cap, w)
    if pad:  # non-aligned caches: pad (masked by ``limit``); production
        # cache capacities are block-aligned so this is normally a no-op
        kw = jnp.pad(kw, ((0, 0), (0, pad), (0, 0)))
        vw = jnp.pad(vw, ((0, 0), (0, pad), (0, 0)))
    n_k = (cap + pad) // block_k
    # ring caches (window attention): every resident slot is valid
    eff_cap = cap if window is None else min(cap, window)

    kernel = functools.partial(_kernel, scale=1.0 / (d ** 0.5),
                               block_k=block_k, n_k=n_k, cap=eff_cap)
    out = decode_call(
        kernel, name="flash_decode", grid=(b, n_k), num_scalar_prefetch=1,
        q_index=lambda bb, ik, lens: (bb, 0, 0),
        kv_index=lambda bb, ik, lens: (bb, ik, 0),
        b=b, hq=hq, width=w, block_k=block_k, dtype=q.dtype,
        interpret=interpret,
    )(cache_len.astype(jnp.int32), spread_heads(q, hkv), kw, vw)
    return gather_heads(out, hkv)
