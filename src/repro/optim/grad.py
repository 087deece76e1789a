"""Gradient utilities for scale-out training.

* microbatched gradient accumulation (scan-carried partial sums, letting XLA
  overlap the per-microbatch reduce-scatter with the next microbatch compute)
* int8 error-feedback gradient compression for slow (cross-pod DP) axes
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def accumulate_grads(loss_fn, params, batch, n_micro: int):
    """Split ``batch`` along axis 0 into ``n_micro`` microbatches and scan,
    accumulating gradients in fp32.  Returns (mean_loss, grads, aux_last)."""
    if n_micro <= 1:
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        return loss, grads, aux

    def reshape(x):
        b = x.shape[0]
        assert b % n_micro == 0, (b, n_micro)
        return x.reshape(n_micro, b // n_micro, *x.shape[1:])

    micro = jax.tree.map(reshape, batch)
    zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def body(carry, mb):
        acc, loss_sum = carry
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, mb)
        acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32), acc, grads)
        return (acc, loss_sum + loss), aux

    (acc, loss_sum), auxes = jax.lax.scan(body, (zero, 0.0), micro)
    grads = jax.tree.map(lambda a: a / n_micro, acc)
    aux_last = jax.tree.map(lambda x: x[-1], auxes)
    return loss_sum / n_micro, grads, aux_last


# ------------------------------------------------------------- compression

def quantize_int8(g):
    """Per-tensor symmetric int8 quantization.  Returns (q, scale)."""
    amax = jnp.max(jnp.abs(g)) + 1e-12
    scale = amax / 127.0
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def compressed_psum(g, axis_name: str, error: jax.Array | None = None):
    """int8 error-feedback all-reduce over ``axis_name`` (inside shard_map).

    Ring-equivalent two-phase scheme with int8 payloads end-to-end:
      1. reduce-scatter phase: per-chunk int8 quantization, all_to_all so
         every peer receives its chunk from everyone, dequantize with the
         TRUE per-(peer, chunk) scales, reduce locally;
      2. all-gather phase: re-quantize the reduced chunk, all_gather.
    Wire cost = 2(k-1)/k x |g| int8 bytes — half of a bf16 ring all-reduce.
    Error feedback keeps the phase-1 quantization residual locally and
    re-adds it next step, making compression unbiased over time.

    Returns (mean_gradient, new_error); shapes match ``g``."""
    k = jax.lax.axis_size(axis_name)
    orig_shape = g.shape
    g32 = g.astype(jnp.float32).reshape(-1)
    if error is not None:
        g32 = g32 + error.astype(jnp.float32).reshape(-1)
    pad = (-g32.size) % k
    if pad:
        g32 = jnp.pad(g32, (0, pad))
    chunks = g32.reshape(k, -1)

    # phase 1: per-chunk quantization + all_to_all
    amax = jnp.max(jnp.abs(chunks), axis=1, keepdims=True) + 1e-12
    scales = amax / 127.0  # (k, 1)
    q = jnp.clip(jnp.round(chunks / scales), -127, 127).astype(jnp.int8)
    new_error = (g32 - (q.astype(jnp.float32) * scales).reshape(-1))
    # row j of the result is peer j's copy of THIS device's chunk
    q_recv = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0)
    s_recv = jax.lax.all_to_all(scales, axis_name, split_axis=0, concat_axis=0)
    partial = jnp.sum(q_recv.astype(jnp.float32) * s_recv, axis=0)  # (m,)

    # phase 2: re-quantize the reduced chunk + all_gather
    amax2 = jnp.max(jnp.abs(partial)) + 1e-12
    s2 = amax2 / 127.0
    q2 = jnp.clip(jnp.round(partial / s2), -127, 127).astype(jnp.int8)
    qs = jax.lax.all_gather(q2, axis_name)          # (k, m)
    ss = jax.lax.all_gather(s2, axis_name)          # (k,)
    total = (qs.astype(jnp.float32) * ss[:, None]).reshape(-1)
    if pad:
        total = total[:-pad]
        new_error = new_error[:-pad]
    mean = (total / k).reshape(orig_shape)
    return mean.astype(g.dtype), new_error.reshape(orig_shape).astype(g.dtype)
