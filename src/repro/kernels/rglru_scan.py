"""RG-LRU linear recurrence (h_t = a_t h_{t-1} + bx_t) as a Pallas TPU kernel.

The recurrence is elementwise over the width dim, so the kernel blocks W into
128-lane tiles (parallel grid axis), streams sequence chunks along the
innermost "arbitrary" axis with the carry in VMEM scratch, and resolves the
within-chunk dependency with a log2(chunk)-depth associative scan on the VPU
(channels vectorize; no MXU needed).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _combine(x, y):
    ax, bx = x
    ay, by = y
    return ax * ay, ay * bx + by


def _kernel(a_ref, b_ref, h_ref, st_ref, carry_scr, *, n_chunks):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        carry_scr[...] = jnp.zeros_like(carry_scr)

    a = a_ref[0].astype(jnp.float32)   # (cl, w)
    bx = b_ref[0].astype(jnp.float32)  # (cl, w)
    bx = bx.at[0].add(a[0] * carry_scr[0])
    ha, hb = jax.lax.associative_scan(_combine, (a, bx), axis=0)
    h_ref[0] = hb.astype(h_ref.dtype)
    carry_scr[0] = hb[-1]

    @pl.when(c_idx == n_chunks - 1)
    def _emit():
        st_ref[0] = hb[-1].astype(st_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "block_w", "interpret"))
def rglru_pallas(a, bx, init_state=None, *, chunk=256, block_w=512,
                 interpret=False):
    """a, bx: (B, S, W).  Returns (h, final_state) like the oracle.
    init_state must be None (the dispatcher falls back otherwise)."""
    assert init_state is None, "rglru_pallas: init_state unsupported; use ref"
    bsz, s, w = a.shape
    chunk = min(chunk, s)
    block_w = min(block_w, w)
    pad_s = (-s) % chunk
    if pad_s:  # pad with a=1, bx=0 (exact no-ops for the recurrence)
        a = jnp.pad(a, ((0, 0), (0, pad_s), (0, 0)), constant_values=1.0)
        bx = jnp.pad(bx, ((0, 0), (0, pad_s), (0, 0)))
    nc = (s + pad_s) // chunk
    nw = pl.cdiv(w, block_w)

    kernel = functools.partial(_kernel, n_chunks=nc)
    h, st = pl.pallas_call(
        kernel,
        grid=(bsz, nw, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_w), lambda b, iw, c: (b, c, iw)),
            pl.BlockSpec((1, chunk, block_w), lambda b, iw, c: (b, c, iw)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_w), lambda b, iw, c: (b, c, iw)),
            pl.BlockSpec((1, block_w), lambda b, iw, c: (b, iw)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s + pad_s, w), bx.dtype),
            jax.ShapeDtypeStruct((bsz, w), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, bx)
    return (h[:, :s] if pad_s else h), st
