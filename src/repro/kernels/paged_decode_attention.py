"""Paged flash-decode: single-token attention over a block-pool KV cache.

Same online-softmax structure and head layout as
``decode_attention.flash_decode`` — one grid instance per batch row handles
every head, with the pool viewed as (N, bs, Hkv*D) — but the KV tiles
stream through VMEM *via the block table* instead of assuming a contiguous
per-sequence cache: the innermost grid axis walks the table's M slots, and
a scalar-prefetch ``block_table`` lets the BlockSpec index_map pick the
physical pool block for each slot before the kernel body runs (the TPU
analogue of vLLM's PagedAttention gather).  Logical position of tile
element o in slot j is ``j * block_size + o``; masking against
``cache_len`` kills both the partial tail block and unallocated table
slots (which conventionally alias the reserved scratch block 0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.decode_attention import (
    decode_call, gather_heads, online_softmax_step, spread_heads)


def _kernel(len_ref, tbl_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
            acc_scr, *, scale, block_size, n_slots):
    bb = pl.program_id(0)
    j = pl.program_id(1)
    # k_ref/v_ref already hold the physical pool block the scalar-prefetch
    # index_map selected via tbl_ref; the shared body only needs the tile's
    # logical key offset and this row's valid length
    online_softmax_step(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                        scale=scale, limit=len_ref[bb],
                        k_start=j * block_size, step=j, n_steps=n_slots)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_flash_decode(q, k_pool, v_pool, block_table, *, cache_len,
                       interpret=False):
    """q: (B, Hq, D); pools: (N, bs, Hkv, D); block_table: (B, M) int32;
    cache_len: (B,) int32.  Returns (B, Hq, D)."""
    b, hq, d = q.shape
    n, bs, hkv, _ = k_pool.shape
    m = block_table.shape[1]
    w = hkv * d
    kernel = functools.partial(_kernel, scale=1.0 / (d ** 0.5),
                               block_size=bs, n_slots=m)
    out = decode_call(
        kernel, grid=(b, m), num_scalar_prefetch=2,
        q_index=lambda bb, j, lens, tbl: (bb, 0, 0),
        kv_index=lambda bb, j, lens, tbl: (tbl[bb * m + j], 0, 0),
        b=b, hq=hq, width=w, block_k=bs, dtype=q.dtype, interpret=interpret,
    )(cache_len.astype(jnp.int32), block_table.astype(jnp.int32).reshape(-1),
      spread_heads(q, hkv), k_pool.reshape(n, bs, w),
      v_pool.reshape(n, bs, w))
    return gather_heads(out, hkv)
