"""Flash-decode: single-token attention over a (ring or linear) KV cache as a
Pallas TPU kernel.

One grid instance handles one batch row and all of its heads. The cache is
read where it lies: the stack of a scan group's caches (L, B, C, Hkv*D),
the layout the model keeps, with a ``layer`` index that arrives by scalar
prefetch, so no per-layer slice, relayout or pad is materialised; or one
layer (B, C, Hkv, D), viewed as a stack of one. A KV tile is (block_k x
Hkv*D): the sequence block on the sublane axis and every KV head together
on the lane axis, which meets the (8, 128) tiling at any head_dim;
``block_k`` is picked from the capacity (``kv_tile``). The query is spread
block-diagonally to (Hq, Hkv*D) (query head h keeps its values in the lane
slot of its KV head and zeros elsewhere), so one (Hq x Hkv*D) . (Hkv*D x
block_k) product scores every head against its own KV head, and the PV
product's diagonal slots are the per-head outputs. ``cache_len`` arrives by
scalar prefetch and masks unwritten slots -- ring caches (window attention)
are handled by the same bound since every resident slot is in-window by
construction. Tiles past the last valid key map to that key's tile, so they
are neither fetched again nor computed: a step reads only the valid prefix.
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
                kv_index, kv_block, b, hq, width, dtype, interpret):
    """The ``pallas_call`` shared by the contiguous and paged decode
    kernels: a (b, kv-tile) grid over (1, Hq, W) queries/outputs and KV
    tiles of ``kv_block`` that the kernel sees as (1, block_k, W), with
    (max, denom, acc) scratch.  ``name`` names the kernel in the compiled
    program and in device traces."""
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, hq, width), q_index),
            pl.BlockSpec(kv_block, kv_index),
            pl.BlockSpec(kv_block, kv_index),
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


def kv_tile(cap: int) -> int:
    """Rows per KV tile: the largest of 512/256/128 that divides the cache
    capacity (every production capacity is a multiple of 128), else the
    largest multiple of 16 below 128 that does, else the whole capacity --
    a tile always divides the cache, so it is never padded."""
    for bk in (512, 256, 128, *range(112, 0, -16)):
        if cap % bk == 0:
            return bk
    return cap


def _kernel(len_ref, last_ref, layer_ref, q_ref, k_ref, v_ref, o_ref, m_scr,
            l_scr, acc_scr, *, scale, block_k, n_k, cap):
    bb = pl.program_id(0)
    ik = pl.program_id(1)
    online_softmax_step(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                        scale=scale, limit=jnp.minimum(len_ref[bb], cap),
                        k_start=ik * block_k, step=ik, n_steps=n_k)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def flash_decode(q, k_cache, v_cache, *, cache_len, layer=None, window=None,
                 interpret=False):
    """q: (B, Hq, D); cache_len: (B,) int32.  Caches: a scan group's stack
    (L, B, C, Hkv*D) with ``layer`` the scalar index of the layer to attend
    over, or one layer (B, C, Hkv, D).  Returns (B, Hq, D)."""
    b, hq, d = q.shape
    if layer is None:
        k_cache, v_cache = (c.reshape(1, *c.shape[:2], -1)
                            for c in (k_cache, v_cache))
        layer = 0
    n_layers, _, cap, w = k_cache.shape
    hkv = w // d
    block_k = kv_tile(cap)
    n_k = cap // block_k
    # ring caches (window attention): every resident slot is valid
    eff_cap = cap if window is None else min(cap, window)
    lens = cache_len.astype(jnp.int32)
    # the tile of each row's last valid key: later tiles map to it
    last = (jnp.clip(lens, 1, eff_cap) - 1) // block_k

    kernel = functools.partial(_kernel, scale=1.0 / (d ** 0.5),
                               block_k=block_k, n_k=n_k, cap=eff_cap)
    out = decode_call(
        kernel, name="flash_decode", grid=(b, n_k), num_scalar_prefetch=3,
        q_index=lambda bb, ik, lens, last, li: (bb, 0, 0),
        kv_index=lambda bb, ik, lens, last, li: (
            li[0], bb, jnp.minimum(ik, last[bb]), 0),
        kv_block=(None, 1, block_k, w),
        b=b, hq=hq, width=w, dtype=q.dtype, interpret=interpret,
    )(lens, last, jnp.asarray(layer, jnp.int32).reshape(1),
      spread_heads(q, hkv), k_cache, v_cache)
    return gather_heads(out, hkv)
