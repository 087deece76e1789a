"""Pallas kernels vs. pure-jnp oracles: shape/dtype sweeps + hypothesis."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.decode_attention import flash_decode
from repro.kernels.flash_attention import flash_mha
from repro.kernels.rglru_scan import rglru_pallas
from repro.kernels.ssd_scan import ssd_pallas

RNG = jax.random.PRNGKey(0)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


# --------------------------------------------------------------- flash mha

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,hq,hkv,d,causal,window", [
    (2, 256, 4, 2, 64, True, None),
    (1, 256, 4, 1, 128, True, 64),
    (2, 128, 2, 2, 32, False, None),
    (1, 384, 6, 3, 64, True, 100),
    (1, 200, 4, 4, 64, True, None),   # non-aligned seq
])
def test_flash_mha_matches_ref(b, s, hq, hkv, d, causal, window, dtype):
    ks = jax.random.split(RNG, 3)
    q = _rand(ks[0], (b, s, hq, d), dtype)
    k = _rand(ks[1], (b, s, hkv, d), dtype)
    v = _rand(ks[2], (b, s, hkv, d), dtype)
    out = flash_mha(q, k, v, causal=causal, window=window, interpret=True)
    want = ref.mha_ref(q, k, v, causal=causal, window=window)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3), st.sampled_from([64, 128, 192]),
       st.sampled_from([(4, 2), (2, 1), (8, 8)]), st.sampled_from([32, 64]),
       st.booleans())
def test_flash_mha_property(b, s, heads, d, causal):
    hq, hkv = heads
    ks = jax.random.split(jax.random.PRNGKey(s * 7 + b), 3)
    q = _rand(ks[0], (b, s, hq, d), jnp.float32)
    k = _rand(ks[1], (b, s, hkv, d), jnp.float32)
    v = _rand(ks[2], (b, s, hkv, d), jnp.float32)
    out = flash_mha(q, k, v, causal=causal, block_q=64, block_k=64,
                    interpret=True)
    want = ref.mha_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=3e-6)


def test_mha_chunked_exact():
    """The q-chunked reference path is exactly the unchunked math."""
    ks = jax.random.split(RNG, 3)
    q = _rand(ks[0], (2, 512, 4, 32), jnp.float32)
    k = _rand(ks[1], (2, 512, 2, 32), jnp.float32)
    v = _rand(ks[2], (2, 512, 2, 32), jnp.float32)
    a = ref.mha_ref(q, k, v, causal=True, window=128, q_chunk=128)
    b = ref.mha_ref(q, k, v, causal=True, window=128, q_chunk=None)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# ------------------------------------------------------------ flash decode

def _decode_case(b, cap, hq, hkv, d, window, lens, stack=None, id=None):
    """``stack``: (layers, layer) -- the cache is a scan group's stack
    (L, B, C, Hkv*D) and the kernel reads layer ``layer`` in place."""
    return pytest.param(b, cap, hq, hkv, d, window, lens, stack,
                        id=id or f"{b}-{cap}-{hq}-{hkv}-{d}-{window}-"
                                 f"{'_'.join(map(str, lens))}-L{stack[0]}")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,cap,hq,hkv,d,window,lens,stack", [
    _decode_case(2, 512, 4, 2, 64, None, [100, 512],
                 id="2-512-4-2-64-None-lens0"),
    _decode_case(2, 128, 8, 1, 128, 128, [50, 4000],
                 id="2-128-8-1-128-128-lens1"),
    _decode_case(1, 300, 6, 3, 32, None, [299], id="1-300-6-3-32-None-lens2"),
    _decode_case(3, 64, 2, 2, 64, 64, [64, 10, 1], id="3-64-2-2-64-64-lens3"),
    # stacked caches: 640 = 5 tiles of 128 (256 does not divide it), rows
    # ending inside the last, partly filled tile and inside tile 1
    _decode_case(2, 640, 14, 2, 64, None, [600, 130], stack=(3, 1)),
    _decode_case(2, 1152, 16, 8, 128, None, [1025, 1], stack=(2, 1)),
    _decode_case(3, 384, 4, 1, 128, None, [384, 129, 2], stack=(4, 3)),
    # a window ring: every slot valid once it has wrapped
    _decode_case(2, 256, 4, 2, 64, 256, [1000, 200], stack=(2, 0)),
])
def test_flash_decode_matches_ref(b, cap, hq, hkv, d, window, lens, stack,
                                  dtype):
    ks = jax.random.split(RNG, 3)
    q = _rand(ks[0], (b, hq, d), dtype)
    n, layer = stack or (1, 0)
    k = _rand(ks[1], (n, b, cap, hkv, d), dtype)
    v = _rand(ks[2], (n, b, cap, hkv, d), dtype)
    cl = jnp.array(lens, jnp.int32)
    if stack is None:
        out = flash_decode(q, k[0], v[0], cache_len=cl, window=window,
                           interpret=True)
    else:
        out = flash_decode(q, k.reshape(n, b, cap, -1),
                           v.reshape(n, b, cap, -1), cache_len=cl,
                           layer=jnp.int32(layer), window=window,
                           interpret=True)
    want = ref.decode_mha_ref(q, k[layer], v[layer], cache_len=cl,
                              window=window)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_kv_tile_divides_the_capacity():
    from repro.kernels.decode_attention import kv_tile
    assert [kv_tile(c) for c in (640, 1152, 1024, 384, 300, 48, 12)] == [
        128, 128, 512, 128, 300, 48, 12]


# ----------------------------------------------------------------- ssd

@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 64, 3, 16, 8, 16),
    (1, 128, 2, 32, 16, 32),
    (1, 64, 1, 64, 128, 64),
])
def test_ssd_matches_ref(b, s, h, p, n, chunk):
    ks = jax.random.split(RNG, 6)
    x = _rand(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(_rand(ks[1], (b, s, h), jnp.float32))
    a_log = _rand(ks[2], (h,), jnp.float32) * 0.5
    bm = _rand(ks[3], (b, s, n), jnp.float32)
    cm = _rand(ks[4], (b, s, n), jnp.float32)
    d = _rand(ks[5], (h,), jnp.float32)
    y1, st1 = ssd_pallas(x, dt, a_log, bm, cm, d, chunk=chunk,
                         return_state=True, interpret=True)
    y2, st2 = ref.ssd_ref(x, dt, a_log, bm, cm, d, chunk=chunk,
                          return_state=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st2), atol=1e-4)


def test_ssd_ref_matches_sequential_recurrence():
    """The chunked oracle equals the naive per-step recurrence."""
    b, s, h, p, n = 1, 32, 2, 8, 4
    ks = jax.random.split(RNG, 6)
    x = _rand(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(_rand(ks[1], (b, s, h), jnp.float32))
    a_log = _rand(ks[2], (h,), jnp.float32) * 0.5
    bm = _rand(ks[3], (b, s, n), jnp.float32)
    cm = _rand(ks[4], (b, s, n), jnp.float32)
    d = _rand(ks[5], (h,), jnp.float32)
    y_chunk, st_chunk = ref.ssd_ref(x, dt, a_log, bm, cm, d, chunk=8,
                                    return_state=True)
    state = jnp.zeros((b, h, p, n))
    ys = []
    for t in range(s):
        y_t, state = ref.ssd_decode_ref(x[:, t], dt[:, t], a_log, bm[:, t],
                                        cm[:, t], d, state)
        ys.append(y_t)
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_seq),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(st_chunk), np.asarray(state),
                               atol=1e-4)


# ----------------------------------------------------------------- rg-lru

@settings(max_examples=8, deadline=None)
@given(st.integers(1, 2), st.sampled_from([33, 64, 100]),
       st.sampled_from([32, 64]), st.sampled_from([16, 32]))
def test_rglru_matches_ref(b, s, w, chunk):
    ks = jax.random.split(jax.random.PRNGKey(b * 100 + s), 2)
    a = jax.nn.sigmoid(_rand(ks[0], (b, s, w), jnp.float32))
    bx = _rand(ks[1], (b, s, w), jnp.float32)
    h1, st1 = rglru_pallas(a, bx, chunk=chunk, interpret=True)
    h2, st2 = ref.rglru_scan_ref(a, bx)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st2), atol=1e-5)


def test_rglru_matches_sequential():
    b, s, w = 2, 17, 8
    ks = jax.random.split(RNG, 2)
    a = jax.nn.sigmoid(_rand(ks[0], (b, s, w), jnp.float32))
    bx = _rand(ks[1], (b, s, w), jnp.float32)
    h, _ = ref.rglru_scan_ref(a, bx)
    cur = jnp.zeros((b, w))
    for t in range(s):
        cur = a[:, t] * cur + bx[:, t]
        np.testing.assert_allclose(np.asarray(h[:, t]), np.asarray(cur),
                                   atol=1e-5)
