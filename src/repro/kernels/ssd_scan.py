"""Mamba-2 SSD (state-space duality) chunked scan as a Pallas TPU kernel.

TPU adaptation of the paper's GPU algorithm: one grid instance owns a
(batch, head) pair; chunks stream along the innermost "arbitrary" grid axis
with the running (P x N) state carried in VMEM scratch.  Within a chunk the
SSD dual form turns the recurrence into three MXU matmuls —
(C·Bᵀ ⊙ decay) · X for the intra-chunk part, C·state for the inter-chunk
part, and the rank-CL state update — so the sequential dimension only appears
across chunks, never inside one.

Emits y and (optionally) the final state for decode handoff.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(x_ref, dt_ref, alog_ref, b_ref, c_ref, d_ref, y_ref, st_ref,
            state_scr, *, n_chunks, chunk):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, :, 0, :].astype(jnp.float32)      # (cl, p)
    dt = dt_ref[0, :, 0].astype(jnp.float32)       # (cl,)
    bm = b_ref[0].astype(jnp.float32)              # (cl, n)
    cm = c_ref[0].astype(jnp.float32)              # (cl, n)
    a = -jnp.exp(alog_ref[0].astype(jnp.float32))  # scalar
    dcoef = d_ref[0].astype(jnp.float32)

    dA = dt * a                                     # (cl,) log-decays
    cums = jnp.cumsum(dA)                           # inclusive
    xdt = x * dt[:, None]

    # intra-chunk: y_diag = (C Bᵀ ⊙ L) xdt, L[t,i]=exp(cums_t - cums_i), t>=i
    seg = cums[:, None] - cums[None, :]
    tri = jax.lax.broadcasted_iota(jnp.int32, seg.shape, 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, seg.shape, 1)
    L = jnp.where(tri, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y = jax.lax.dot_general(scores * L, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y_off = exp(cums) * (C · stateᵀ)
    state = state_scr[...]                          # (p, n)
    y += jnp.exp(cums)[:, None] * jax.lax.dot_general(
        cm, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: state' = exp(cums[-1]) state + (decay_to_end ⊙ xdt)ᵀ B
    decay_end = jnp.exp(cums[-1] - cums)
    state_scr[...] = state * jnp.exp(cums[-1]) + jax.lax.dot_general(
        xdt * decay_end[:, None], bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    y_ref[0, :, 0, :] = (y + dcoef * x).astype(y_ref.dtype)

    @pl.when(c_idx == n_chunks - 1)
    def _emit_state():
        st_ref[0, 0] = state_scr[...].astype(st_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "return_state",
                                             "interpret"))
def ssd_pallas(x, dt, a_log, b_mat, c_mat, d_vec, *, chunk, init_state=None,
               return_state=False, interpret=False):
    """Shapes as in ``ref.ssd_ref``.  init_state must be None (prefill from
    scratch); the dispatcher falls back to the oracle otherwise."""
    assert init_state is None, "ssd_pallas: init_state unsupported; use ref"
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    kernel = functools.partial(_kernel, n_chunks=nc, chunk=chunk)
    y, st = pl.pallas_call(
        kernel,
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda b, hh, c: (b, c, hh, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, hh, c: (b, c, hh)),
            pl.BlockSpec((1,), lambda b, hh, c: (hh,)),
            pl.BlockSpec((1, chunk, n), lambda b, hh, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, hh, c: (b, c, 0)),
            pl.BlockSpec((1,), lambda b, hh, c: (hh,)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda b, hh, c: (b, c, hh, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b, hh, c: (b, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, a_log, b_mat, c_mat, d_vec)
    if return_state:
        return y, st
    return y
