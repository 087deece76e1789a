"""Grouped expert FFN (dropless MoE): ragged sort-by-expert grouped GEMM.

The dropless dispatch hands this kernel the expert-sorted rows ``xs`` and the
ragged per-expert ``group_sizes`` — no ``(E, C)`` capacity padding, so the
kernel does work proportional to the *real* token count (a t=2 decode step
runs 2·k rows, not ``E × max(8, capacity)`` padded ones).

Structure (the TPU analogue of MegaBlocks' grouped GEMM):

* Rows are tiled into ``block_rows`` m-tiles.  A tile that straddles a group
  boundary is processed once per group it intersects, so the worst-case
  logical grid is ``tiles_m + E - 1`` *work units*.  ``ref.group_metadata``
  (shared with the jnp oracle, which scans the same units) builds, per unit,
  the owning expert, the m-tile, the group's [lo, hi) row range (for masking
  rows of other groups / padding), and a first-visit flag.
* The metadata rides in as **scalar prefetch** (same pattern as
  ``paged_flash_decode``'s block table): the BlockSpec index_maps read
  ``unit_tile``/``unit_group`` to pick which row tile and which expert's
  weight slabs to DMA before the body runs.
* Grid is ``(units, F tiles)`` with F innermost; the output block index only
  depends on the unit's m-tile, so revisits (across F tiles and across
  boundary-spanning units) are consecutive and the out VMEM block doubles as
  the fp32 accumulator — zeroed on a unit's first visit to its tile, added to
  otherwise.

All three matmuls accumulate in fp32 (``preferred_element_type``) and the
kernel returns fp32, matching ``ref.grouped_ffn_ref`` bit-for-bit at fp32
inputs; the combine caller casts to the model dtype once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import numpy as np

from repro.kernels.ref import ACTS, expert_ids_of, group_metadata, row_tiles


def _kernel(ug_ref, ut_ref, lo_ref, hi_ref, first_ref, x_ref, wg_ref, wi_ref,
            wo_ref, o_ref, *, bn, act_fn):
    g = pl.program_id(0)
    f = pl.program_id(1)
    first = (first_ref[g] == 1) & (f == 0)

    @pl.when(first)
    def _init():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    # padding units carry an empty [0, 0) row range: no matmuls for them
    # (their block indices alias the previous unit's, so no DMAs either)
    @pl.when(lo_ref[g] < hi_ref[g])
    def _accum():
        rows = ut_ref[g] * bn + jax.lax.broadcasted_iota(
            jnp.int32, (bn, 1), 0)
        mask = (rows >= lo_ref[g]) & (rows < hi_ref[g])  # (bn, 1)
        x = x_ref[...].astype(jnp.float32)               # (bn, D)
        wg = wg_ref[0].astype(jnp.float32)               # (D, bf)
        wi = wi_ref[0].astype(jnp.float32)
        wo = wo_ref[0].astype(jnp.float32)               # (bf, D)
        gate = act_fn(jnp.dot(x, wg, preferred_element_type=jnp.float32))
        h = gate * jnp.dot(x, wi, preferred_element_type=jnp.float32)
        y = jnp.dot(h, wo, preferred_element_type=jnp.float32)  # (bn, D)
        o_ref[...] = o_ref[...] + jnp.where(mask, y, 0.0)


def _forward(xs, group_sizes, w_gate, w_in, w_out, act, block_rows, block_ff,
             interpret):
    n, d = xs.shape
    e, _, f = w_gate.shape
    bn, n_pad = row_tiles(n, block_rows)
    bf = min(block_ff, f)
    while f % bf:  # halve until it tiles F (arctic: 4864 -> 256)
        bf //= 2
    if n_pad != n:
        xs = jnp.pad(xs, ((0, n_pad - n), (0, 0)))
    meta = group_metadata(group_sizes, n_pad, bn)
    units = meta[0].shape[0]

    kernel = functools.partial(_kernel, bn=bn, act_fn=ACTS[act])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(units, f // bf),
        in_specs=[
            pl.BlockSpec((bn, d),
                         lambda g, j, ug, ut, lo, hi, fi: (ut[g], 0)),
            pl.BlockSpec((1, d, bf),
                         lambda g, j, ug, ut, lo, hi, fi: (ug[g], 0, j)),
            pl.BlockSpec((1, d, bf),
                         lambda g, j, ug, ut, lo, hi, fi: (ug[g], 0, j)),
            pl.BlockSpec((1, bf, d),
                         lambda g, j, ug, ut, lo, hi, fi: (ug[g], j, 0)),
        ],
        out_specs=pl.BlockSpec((bn, d),
                               lambda g, j, ug, ut, lo, hi, fi: (ut[g], 0)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_pad, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*meta, xs, w_gate, w_in, w_out)
    return out[:n]


# --- backward (custom_vjp): the Pallas tier is trainable ------------------
#
# The fwd runs the Mosaic kernel above; the bwd recomputes the activations
# remat-style in the jnp gather regime (per-row expert weight gather — the
# per-row math is identical to the kernel's, so grads are exact w.r.t. the
# fp32 forward) and reduces weight grads per expert with ``segment_sum``
# over the expert-sorted row ids.  ``group_sizes`` is integer-valued and
# gets a ``float0`` cotangent.


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _grouped_ffn_diff(xs, group_sizes, w_gate, w_in, w_out, act, block_rows,
                      block_ff, interpret):
    return _forward(xs, group_sizes, w_gate, w_in, w_out, act, block_rows,
                    block_ff, interpret)


def _diff_fwd(xs, group_sizes, w_gate, w_in, w_out, act, block_rows,
              block_ff, interpret):
    out = _forward(xs, group_sizes, w_gate, w_in, w_out, act, block_rows,
                   block_ff, interpret)
    return out, (xs, group_sizes, w_gate, w_in, w_out)


def _diff_bwd(act, block_rows, block_ff, interpret, res, g):
    del block_rows, block_ff, interpret
    xs, group_sizes, w_gate, w_in, w_out = res
    n, _ = xs.shape
    e = w_gate.shape[0]
    f32 = jnp.float32
    act_fn = ACTS[act]
    eid = expert_ids_of(group_sizes, n)
    in_group = jnp.arange(n) < jnp.sum(group_sizes)

    x = xs.astype(f32)
    wg = w_gate[eid].astype(f32)   # (N, D, F)
    wi = w_in[eid].astype(f32)
    wo = w_out[eid].astype(f32)    # (N, F, D)
    pre_g = jnp.einsum("nd,ndf->nf", x, wg)
    pre_i = jnp.einsum("nd,ndf->nf", x, wi)
    a, act_vjp = jax.vjp(act_fn, pre_g)
    h = a * pre_i

    g = jnp.where(in_group[:, None], g.astype(f32), 0.0)
    dh = jnp.einsum("nd,nfd->nf", g, wo)
    dpre_i = dh * a
    dpre_g = act_vjp(dh * pre_i)[0]
    dx = (jnp.einsum("nf,ndf->nd", dpre_g, wg)
          + jnp.einsum("nf,ndf->nd", dpre_i, wi))
    dwg = jax.ops.segment_sum(x[:, :, None] * dpre_g[:, None, :], eid, e)
    dwi = jax.ops.segment_sum(x[:, :, None] * dpre_i[:, None, :], eid, e)
    dwo = jax.ops.segment_sum(h[:, :, None] * g[:, None, :], eid, e)
    return (dx.astype(xs.dtype),
            np.zeros(group_sizes.shape, jax.dtypes.float0),
            dwg.astype(w_gate.dtype), dwi.astype(w_in.dtype),
            dwo.astype(w_out.dtype))


_grouped_ffn_diff.defvjp(_diff_fwd, _diff_bwd)


@functools.partial(jax.jit, static_argnames=("act", "block_rows", "block_ff",
                                             "interpret"))
def grouped_ffn(xs, group_sizes, w_gate, w_in, w_out, *, act="silu",
                block_rows=128, block_ff=512, interpret=False):
    """xs: (N, D) expert-sorted rows; group_sizes: (E,) int32 summing to N;
    w_gate/w_in: (E, D, F); w_out: (E, F, D).  Returns (N, D) float32 —
    row i through expert ``expert_ids_of(group_sizes, N)[i]`` only.
    Differentiable: forward runs the Pallas kernel, backward the jnp
    recompute above (grads match ``jax.grad`` of ``grouped_ffn_ref`` to
    fp32 tolerance — asserted in tests/test_moe.py)."""
    return _grouped_ffn_diff(xs, group_sizes, w_gate, w_in, w_out, act,
                             block_rows, block_ff, interpret)
