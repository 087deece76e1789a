"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

Interpret mode does not check the chip's (8, 128) block tiling, SMEM/VMEM
placement or Mosaic's lowering rules; these tests run the TPU compiler
against a described (not attached) ``v5e:2x2`` topology at qwen2-0.5b
widths (14 query heads, 2 KV heads, head_dim 64, bf16).  Nothing runs, so
they say nothing about results or speed.

The topology is described inside a fixture, never at import, and the
persistent compilation cache is off around the compiles (an entry written
for a described chip cannot be read back without one).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, HQ, HKV, D = 8, 14, 2, 64  # qwen2-0.5b attention widths
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("seq,positions", [(256, False), (256, True),
                                           (5, True)])
def test_flash_mha_compiles(one_chip, seq, positions):
    """Prefill / train widths (seq 256), and the verify-step shape (5
    queries against a 256-key cache with explicit positions)."""
    from repro.kernels.flash_attention import flash_mha
    skv = 256

    def fn(q, k, v, qp, kp):
        if not positions:
            qp = kp = None
        return flash_mha(q, k, v, causal=True, q_positions=qp,
                         kv_positions=kp)

    _compile(fn, one_chip, ((B, seq, HQ, D), BF16), ((B, skv, HKV, D), BF16),
             ((B, skv, HKV, D), BF16), ((B, seq), jnp.int32),
             ((B, skv), jnp.int32))


def test_flash_decode_compiles(one_chip):
    from repro.kernels.decode_attention import flash_decode
    cap = 256
    _compile(lambda q, k, v, n: flash_decode(q, k, v, cache_len=n), one_chip,
             ((B, HQ, D), BF16), ((B, cap, HKV, D), BF16),
             ((B, cap, HKV, D), BF16), ((B,), jnp.int32))


def test_paged_flash_decode_compiles(one_chip):
    from repro.kernels.paged_decode_attention import paged_flash_decode
    bs, m = 16, 16
    n = B * m + 1
    _compile(lambda q, k, v, t, c: paged_flash_decode(q, k, v, t, cache_len=c),
             one_chip, ((B, HQ, D), BF16), ((n, bs, HKV, D), BF16),
             ((n, bs, HKV, D), BF16), ((B, m), jnp.int32), ((B,), jnp.int32))


def test_flash_mha_varlen_compiles(one_chip):
    from repro.kernels.varlen_attention import flash_mha_varlen
    t = 2048
    _compile(lambda q, k, v, cu: flash_mha_varlen(q, k, v, cu), one_chip,
             ((t, HQ, D), BF16), ((t, HKV, D), BF16), ((t, HKV, D), BF16),
             ((B + 1,), jnp.int32))
