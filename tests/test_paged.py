"""Paged KV cache + continuous-batching engine: allocator invariants,
paged-attention kernel parity with the contiguous decode kernel, and
scheduler behaviour (out-of-order completion, block reuse, preemption)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.kernels import ops
from repro.launch.serve import BatchServer, ContinuousBatchServer, build_server
from repro.models import (BlockAllocator, full_buffer_bytes, generate,
                          init_params, kv_pool_bytes, needed_blocks)

RNG = jax.random.PRNGKey(0)


# ---------------------------------------------------------------- allocator

def test_allocator_invariants():
    a = BlockAllocator(8, block_size=16)
    assert a.free_count == 7  # block 0 reserved
    ids = a.alloc(3)
    assert 0 not in ids and len(set(ids)) == 3
    assert a.used_count == 3 and a.peak == 3
    more = a.alloc(4)
    assert not set(ids) & set(more)
    assert a.free_count == 0 and a.peak == 7
    with pytest.raises(MemoryError):
        a.alloc(1)
    a.free(ids)
    assert a.free_count == 3 and a.peak == 7  # peak is a high-water mark
    with pytest.raises(ValueError):
        a.free([ids[0]])  # double free
    with pytest.raises(ValueError):
        a.free([0])  # reserved block was never handed out
    reused = a.alloc(3)
    assert set(reused) == set(ids)  # freed blocks are reused


def test_needed_blocks():
    assert needed_blocks(1, 16) == 1
    assert needed_blocks(16, 16) == 1
    assert needed_blocks(17, 16) == 2


# ------------------------------------------------------------ kernel parity

@pytest.mark.parametrize("impl", ["reference", "pallas_interpret"])
@pytest.mark.parametrize("lens", [(1, 17, 40), (8, 8, 33)])
def test_paged_decode_matches_contiguous(impl, lens):
    """paged_decode_mha over a shuffled block pool == decode_mha over the
    gathered contiguous cache, on ragged cache lengths (fp32 tol)."""
    b, hq, hkv, d, bs, m = 3, 8, 2, 16, 8, 5
    n = 1 + b * m
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32)
    k_pool = jax.random.normal(ks[1], (n, bs, hkv, d), jnp.float32)
    v_pool = jax.random.normal(ks[2], (n, bs, hkv, d), jnp.float32)
    perm = np.random.default_rng(0).permutation(np.arange(1, n))
    tbl = jnp.asarray(perm.reshape(b, m), jnp.int32)  # non-contiguous blocks
    cache_len = jnp.asarray(lens, jnp.int32)

    out = ops.paged_decode_mha(q, k_pool, v_pool, tbl, cache_len=cache_len,
                               impl=impl)
    k_c = k_pool[tbl].reshape(b, m * bs, hkv, d)
    v_c = v_pool[tbl].reshape(b, m * bs, hkv, d)
    ref = ops.decode_mha(q, k_c, v_c, cache_len=cache_len, impl="reference")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


def test_paged_decode_masks_unallocated_slots():
    """Garbage in table entries past cache_len (scratch block 0) must not
    leak into the output."""
    b, hq, hkv, d, bs, m = 2, 4, 2, 16, 8, 4
    n = 1 + b * m
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32)
    k_pool = jax.random.normal(ks[1], (n, bs, hkv, d), jnp.float32)
    v_pool = jax.random.normal(ks[2], (n, bs, hkv, d), jnp.float32)
    tbl = jnp.asarray(np.arange(1, n).reshape(b, m), jnp.int32)
    lens = jnp.asarray([5, 11], jnp.int32)
    base = ops.paged_decode_mha(q, k_pool, v_pool, tbl, cache_len=lens)
    # point every slot past the live prefix at scratch block 0 instead
    live = needed_blocks(11, bs)
    tbl0 = jnp.where(jnp.arange(m)[None, :] < live, tbl, 0)
    k_pool = k_pool.at[0].set(1e4)  # poison scratch
    v_pool = v_pool.at[0].set(-1e4)
    out = ops.paged_decode_mha(q, k_pool, v_pool, tbl0, cache_len=lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base), atol=2e-6)


@pytest.mark.parametrize("starts", [(0, 9, 30), (12, 3, 35)])
def test_paged_verify_matches_reference_at_offset_positions(starts):
    """The speculative verify step's kernel tier honours the explicit
    query positions: K verify tokens starting mid-cache attend exactly the
    logical positions <= their own, as the reference does."""
    b, kk, hq, hkv, d, bs, m = 3, 5, 8, 2, 16, 8, 5
    n = 1 + b * m
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (b, kk, hq, d), jnp.float32)
    k_pool = jax.random.normal(ks[1], (n, bs, hkv, d), jnp.float32)
    v_pool = jax.random.normal(ks[2], (n, bs, hkv, d), jnp.float32)
    perm = np.random.default_rng(1).permutation(np.arange(1, n))
    tbl = jnp.asarray(perm.reshape(b, m), jnp.int32)
    pos = (jnp.asarray(starts, jnp.int32)[:, None]
           + jnp.arange(kk, dtype=jnp.int32)[None])
    ref = ops.paged_verify_mha(q, k_pool, v_pool, tbl, q_positions=pos,
                               impl="reference")
    out = ops.paged_verify_mha(q, k_pool, v_pool, tbl, q_positions=pos,
                               impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ----------------------------------------------------------------- serving

@pytest.fixture(scope="module")
def setup():
    cfg = ARCHS["qwen2-0.5b"].reduced()
    return cfg, init_params(RNG, cfg)


def _prompts(cfg, n, plen=16, seed=0):
    r = np.random.default_rng(seed)
    return [np.asarray(r.integers(1, cfg.vocab_size, plen), np.int32)
            for _ in range(n)]


def test_continuous_matches_generate_greedy(setup):
    """Bucket-exact prompts, greedy: the paged engine must reproduce the
    contiguous-cache generate() tokens and logprobs per request."""
    cfg, params = setup
    prompts = _prompts(cfg, 4)
    new = [3, 9, 5, 2]
    srv = ContinuousBatchServer(cfg, params, n_slots=2, kv_block_size=8,
                                max_prompt=16, max_new=16)
    toks, lps = srv.serve(prompts, rng=None, max_new=new)
    for i, pr in enumerate(prompts):
        out = generate(params, cfg, {"tokens": jnp.asarray(pr[None])},
                       num_new_tokens=new[i], rng=None)
        np.testing.assert_array_equal(toks[i], np.asarray(out["tokens"][0]))
        np.testing.assert_allclose(lps[i], np.asarray(out["logprobs"][0]),
                                   atol=1e-4)


@pytest.mark.parametrize("arch", ["gemma3-1b", "recurrentgemma-9b",
                                  "mamba2-1.3b"])
def test_continuous_greedy_parity_window_and_recurrent(arch):
    """Window ring caches and recurrent (LRU/SSD) states ride through the
    paged engine unchanged — greedy parity per family."""
    cfg = ARCHS[arch].reduced()
    params = init_params(RNG, cfg)
    prompts = _prompts(cfg, 3, seed=1)
    new = [4, 8, 2]
    srv = ContinuousBatchServer(cfg, params, n_slots=2, kv_block_size=8,
                                max_prompt=16, max_new=8)
    toks, _ = srv.serve(prompts, rng=None, max_new=new)
    for i, pr in enumerate(prompts):
        out = generate(params, cfg, {"tokens": jnp.asarray(pr[None])},
                       num_new_tokens=new[i], rng=None)
        np.testing.assert_array_equal(toks[i], np.asarray(out["tokens"][0]))


def test_short_request_completes_before_long(setup):
    """Continuous batching retires a short request while a long one is
    still decoding, and its freed blocks are reused by a queued request."""
    cfg, params = setup
    prompts = _prompts(cfg, 3)
    short, long_, queued = 0, 1, 2
    bs = 4
    nb_prompt = needed_blocks(16, bs)  # 4 blocks per prompt
    # pool: exactly short(4+1) + long(4+5) usable -> the queued request can
    # only be admitted out of blocks the short one released
    pool = 1 + (nb_prompt + 1) + (nb_prompt + 5)
    srv = ContinuousBatchServer(cfg, params, n_slots=2, kv_block_size=bs,
                                max_kv_blocks=pool, max_prompt=16,
                                max_new=20)
    toks, _ = srv.serve([prompts[short], prompts[long_], prompts[queued]],
                        rng=None, max_new=[2, 20, 2])
    st = srv.stats()
    assert st["completion_order"][0] == short
    assert st["completion_order"][-1] == long_  # long finishes last
    assert st["preemptions"] == 0
    assert st["peak_blocks"] <= pool - 1
    assert len(toks[short]) == 2 and len(toks[long_]) == 20
    assert len(toks[queued]) == 2
    # block reuse is what made admission possible at this pool size; also
    # check the queued request decoded correctly after reuse
    out = generate(params, cfg, {"tokens": jnp.asarray(prompts[queued][None])},
                   num_new_tokens=2, rng=None)
    np.testing.assert_array_equal(toks[queued], np.asarray(out["tokens"][0]))


def test_preemption_requeues_and_recovers(setup):
    """When the pool runs dry mid-flight, the youngest request is
    preempted (blocks freed, recomputed later) and still returns the
    right tokens."""
    cfg, params = setup
    prompts = _prompts(cfg, 2)
    bs = 4
    # room for both prompts but not both generations: 2*(4 blocks) + 2
    pool = 1 + 2 * needed_blocks(16, bs) + 2
    srv = ContinuousBatchServer(cfg, params, n_slots=2, kv_block_size=bs,
                                max_kv_blocks=pool, max_prompt=16,
                                max_new=12)
    toks, _ = srv.serve(prompts, rng=None, max_new=[12, 12])
    assert srv.stats()["preemptions"] >= 1
    for i, pr in enumerate(prompts):
        out = generate(params, cfg, {"tokens": jnp.asarray(pr[None])},
                       num_new_tokens=12, rng=None)
        np.testing.assert_array_equal(toks[i], np.asarray(out["tokens"][0]))


def test_eos_retires_slot_early(setup):
    """A row that samples eos_id completes immediately (output includes
    the EOS token) and frees its slot."""
    cfg, params = setup
    prompts = _prompts(cfg, 2)
    # greedy decode to find what token the first step produces, then use it
    # as the "EOS" for one request
    probe = generate(params, cfg, {"tokens": jnp.asarray(prompts[0][None])},
                     num_new_tokens=2, rng=None)
    eos = int(np.asarray(probe["tokens"])[0, 1])
    srv = ContinuousBatchServer(cfg, params, n_slots=2, kv_block_size=8,
                                max_prompt=16, max_new=10, eos_id=eos)
    toks, _ = srv.serve(prompts, rng=None, max_new=[10, 10])
    assert toks[0][-1] == eos and len(toks[0]) <= 2


def test_sampled_serving_runs(setup):
    cfg, params = setup
    srv = ContinuousBatchServer(cfg, params, n_slots=2, kv_block_size=8,
                                max_prompt=16, max_new=6, top_k=8,
                                top_p=0.95)
    toks, lps = srv.serve(_prompts(cfg, 3), rng=jax.random.PRNGKey(3),
                          max_new=6)
    for t, l in zip(toks, lps):
        assert len(t) == 6 and np.all(np.asarray(l) <= 0)


def test_continuous_runs_on_pallas_interpret(setup):
    """The paged decode kernel body validates on CPU via interpret mode."""
    cfg, params = setup
    srv = ContinuousBatchServer(cfg, params, n_slots=2, kv_block_size=8,
                                max_prompt=16, max_new=3,
                                impl="pallas_interpret")
    toks, _ = srv.serve(_prompts(cfg, 2), rng=None, max_new=3)
    ref = ContinuousBatchServer(cfg, params, n_slots=2, kv_block_size=8,
                                max_prompt=16, max_new=3)
    rtoks, _ = ref.serve(_prompts(cfg, 2), rng=None, max_new=3)
    for a, b in zip(toks, rtoks):
        np.testing.assert_array_equal(a, b)


def test_kv_accounting_paged_below_full(setup):
    """The paged pool's peak footprint stays below the run-to-completion
    baseline's full-length buffers for a long-tail workload."""
    cfg, params = setup
    r = np.random.default_rng(2)
    n_req, max_new = 8, 48
    prompts = _prompts(cfg, n_req)
    new = np.minimum(r.geometric(1 / 6.0, n_req), max_new).tolist()
    srv = ContinuousBatchServer(cfg, params, n_slots=4, kv_block_size=8,
                                max_prompt=16, max_new=max_new)
    srv.serve(prompts, rng=None, max_new=new)
    paged = kv_pool_bytes(cfg, srv.alloc.peak, srv.bs, cfg.dtype)
    full = full_buffer_bytes(cfg, n_req, 16 + max_new, cfg.dtype)
    assert paged < full, (paged, full)


def test_oversize_request_rejected_at_submission(setup):
    """A request that can never fit is rejected before any work starts —
    it must not raise mid-flight and poison in-flight requests."""
    cfg, params = setup
    srv = ContinuousBatchServer(cfg, params, n_slots=2, kv_block_size=8,
                                max_prompt=16, max_new=8)
    good, bad = _prompts(cfg, 2)
    with pytest.raises(ValueError, match="exceeds max_len"):
        srv.serve([good, bad], rng=None, max_new=[4, srv.max_len])
    assert not srv.queue and not srv._active()  # nothing enqueued
    toks, _ = srv.serve([good], rng=None, max_new=[4])  # still serviceable
    assert len(toks[0]) == 4


def test_build_server_modes(setup):
    cfg, params = setup
    from repro.rlhf.experiment import ExperimentConfig
    exp = ExperimentConfig(serve_mode="continuous", kv_block_size=8)
    assert isinstance(build_server(cfg, params, exp, max_prompt=16,
                                   max_new=4), ContinuousBatchServer)
    exp = ExperimentConfig(serve_mode="bucketed")
    assert isinstance(build_server(cfg, params, exp, max_new=4), BatchServer)
    exp = ExperimentConfig(serve_mode="nope")
    with pytest.raises(ValueError):
        build_server(cfg, params, exp)
