"""Dropless MoE dispatch: grouped-kernel tier parity (ragged offsets, empty
experts, all-to-one), cohort independence, capacity-path drop semantics
(post-drop weight renormalization), fp32 combine, and aux-loss gating on the
serving paths."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.kernels import ref
from repro.kernels.grouped_expert import grouped_ffn
from repro.models import decode_step, init_params, prefill, synth_batch
from repro.models import moe as M

RNG = jax.random.PRNGKey(0)


def _weights(key, e, d, f):
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (e, d, f)) * 0.1,
            jax.random.normal(ks[1], (e, d, f)) * 0.1,
            jax.random.normal(ks[2], (e, f, d)) * 0.1)


def _loop_oracle(xs, sizes, wg, wi, wo):
    """Naive per-row numpy loop: row i through its own expert only."""
    eids = np.repeat(np.arange(len(sizes)), sizes)
    out = np.zeros((xs.shape[0], wo.shape[2]), np.float32)
    for i, e in enumerate(eids):
        x = np.asarray(xs[i], np.float32)
        g = x @ np.asarray(wg[e], np.float32)
        g = g / (1.0 + np.exp(-g))  # silu
        h = g * (x @ np.asarray(wi[e], np.float32))
        out[i] = h @ np.asarray(wo[e], np.float32)
    return out


# ------------------------------------------------------- grouped kernel tiers

@pytest.mark.parametrize("e,n,d,f,sizes", [
    (4, 40, 64, 32, [10, 0, 25, 5]),     # ragged + an empty expert
    (3, 7, 16, 8, [7, 0, 0]),            # all tokens to one expert (first)
    (5, 33, 32, 16, [0, 0, 33, 0, 0]),   # all to one (middle), n % bn != 0
    (2, 129, 32, 48, [64, 65]),          # boundary straddles a row tile
    (4, 16, 16, 8, [4, 4, 4, 4]),        # exactly tile-aligned groups
])
def test_grouped_ffn_tiers_match(e, n, d, f, sizes):
    ks = jax.random.split(jax.random.PRNGKey(n), 2)
    xs = jax.random.normal(ks[0], (n, d), jnp.float32)
    wg, wi, wo = _weights(ks[1], e, d, f)
    gs = jnp.array(sizes, jnp.int32)
    want = ref.grouped_ffn_ref(xs, gs, wg, wi, wo)
    np.testing.assert_allclose(np.asarray(want),
                               _loop_oracle(xs, sizes, wg, wi, wo), atol=1e-4)
    # the large-shape regime (work-unit scan) computes the same function
    scanned = ref.grouped_ffn_ref(xs, gs, wg, wi, wo, block_rows=16,
                                  gather_limit=0)
    np.testing.assert_allclose(np.asarray(scanned), np.asarray(want),
                               atol=1e-5)
    # small tiles force boundary-spanning work units and F-tiling
    got = grouped_ffn(xs, gs, wg, wi, wo, block_rows=16, block_ff=8,
                      interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_grouped_ffn_reference_regimes_zero_tail_rows():
    """Out-of-contract group_sizes summing to < N: both reference regimes
    agree and zero the tail rows instead of routing them anywhere."""
    e, n, d, f = 3, 16, 8, 4
    xs = jax.random.normal(RNG, (n, d), jnp.float32)
    wg, wi, wo = _weights(jax.random.PRNGKey(7), e, d, f)
    gs = jnp.array([5, 0, 6], jnp.int32)  # sums to 11 < 16
    gathered = ref.grouped_ffn_ref(xs, gs, wg, wi, wo)
    scanned = ref.grouped_ffn_ref(xs, gs, wg, wi, wo, block_rows=8,
                                  gather_limit=0)
    np.testing.assert_allclose(np.asarray(gathered), np.asarray(scanned),
                               atol=1e-5)
    assert np.all(np.asarray(gathered[11:]) == 0.0)
    assert np.abs(np.asarray(gathered[:11])).max() > 0


def test_grouped_ffn_ragged_offsets_select_experts():
    """Shifting one row across a group boundary changes only that row."""
    e, n, d, f = 3, 12, 8, 4
    xs = jax.random.normal(RNG, (n, d), jnp.float32)
    wg, wi, wo = _weights(jax.random.PRNGKey(1), e, d, f)
    a = ref.grouped_ffn_ref(xs, jnp.array([4, 4, 4]), wg, wi, wo)
    b = ref.grouped_ffn_ref(xs, jnp.array([5, 3, 4]), wg, wi, wo)
    diff = np.abs(np.asarray(a - b)).max(axis=1)
    assert diff[4] > 0  # row 4 moved from expert 1 to expert 0
    assert np.all(diff[np.arange(n) != 4] == 0)


def test_grouped_ffn_backward_matches_reference_grad():
    """grouped_ffn is trainable: jax.grad through the interpret tier (the
    custom_vjp) matches jax.grad through the pure-JAX reference for inputs
    and all three expert weights, including an empty expert group and
    out-of-group tail rows (which must receive zero gradient)."""
    e, n, d, f = 4, 24, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    xs = jax.random.normal(ks[0], (n, d), jnp.float32)
    wg, wi, wo = _weights(ks[1], e, d, f)
    gs = jnp.array([9, 0, 11, 2], jnp.int32)  # sums to 22 < 24: tail rows

    def loss(fn, xs, wg, wi, wo):
        cot = jnp.sin(jnp.arange(n * d, dtype=jnp.float32)).reshape(n, d)
        return jnp.sum(fn(xs, gs, wg, wi, wo) * cot)

    g_ref = jax.grad(lambda *a: loss(ref.grouped_ffn_ref, *a),
                     argnums=(0, 1, 2, 3))(xs, wg, wi, wo)
    g_krn = jax.grad(
        lambda *a: loss(lambda *b: grouped_ffn(*b, block_rows=16, block_ff=8,
                                               interpret=True), *a),
        argnums=(0, 1, 2, 3))(xs, wg, wi, wo)
    for a, b in zip(g_ref, g_krn):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-5)
    # tail rows past sum(group_sizes) belong to no expert: zero input grad
    assert np.all(np.asarray(g_krn[0][22:]) == 0.0)
    # the empty expert's weights receive exactly zero gradient
    for gw in g_krn[1:]:
        assert np.all(np.asarray(gw[1]) == 0.0)


def test_dropless_routes_real_tokens_only_when_packed():
    """Packed (total_tokens,) MoE: every expert row is a real token —
    sum(group_sizes) == T_real * top_k, strictly fewer rows than the padded
    (B, S) layout dispatches — and outputs match the padded layout's on the
    valid region to fp tolerance (routing is per-token, so packing must not
    change any token's expert assignment or combine weights)."""
    from repro.data import packing
    cfg = _moe_cfg()
    p = M.moe_init(RNG, cfg)
    lens = [3, 12, 1, 7]
    b, s = len(lens), max(lens)
    x = jax.random.normal(jax.random.PRNGKey(9), (b, s, cfg.d_model),
                          jnp.float32)
    xp = packing.pack(x, lens)[None]  # (1, T, D)
    t_real = xp.shape[1]
    assert t_real == sum(lens) and t_real < b * s

    def rows_dispatched(xin):
        xf = xin.reshape(-1, cfg.d_model)
        _, _, top_i = M._router(p, cfg, xf)
        gs = jnp.zeros((cfg.n_experts,), jnp.int32).at[
            top_i.reshape(-1)].add(1)
        return int(gs.sum())

    assert rows_dispatched(xp) == t_real * cfg.top_k
    assert rows_dispatched(x) == b * s * cfg.top_k  # padded wastes rows
    # padded expert rows in the packed dispatch: none, by construction
    assert rows_dispatched(xp) - t_real * cfg.top_k == 0

    y_packed, _ = M.moe_apply(p, cfg, xp)
    y_padded, _ = M.moe_apply(p, cfg, x)
    np.testing.assert_allclose(
        np.asarray(y_packed[0]),
        np.asarray(packing.pack(y_padded, lens)), atol=2e-5)


# --------------------------------------------------------- cohort independence

def _moe_cfg(arch="granite-moe-1b-a400m", **kw):
    return dataclasses.replace(ARCHS[arch].reduced(), **kw)


@pytest.mark.parametrize("arch", ["granite-moe-1b-a400m", "arctic-480b"])
def test_dropless_is_cohort_independent(arch):
    """A token's MoE output agrees (to fp tolerance) whether computed in a
    (B, S) batch or alone in a (1, 1) decode-shaped cohort — the property
    that makes rollout logprobs match the trainer's recomputation."""
    cfg = _moe_cfg(arch)
    p = M.moe_init(RNG, cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, cfg.d_model),
                          jnp.float32)
    full, _ = M.moe_apply(p, cfg, x)
    for bi in range(2):
        for si in range(0, 12, 5):
            one, _ = M.moe_apply(p, cfg, x[bi:bi + 1, si:si + 1])
            np.testing.assert_allclose(np.asarray(one[0, 0]),
                                       np.asarray(full[bi, si]), atol=2e-5)


def test_capacity_is_cohort_dependent_when_overflowing():
    """Sanity check that the legacy path still shows the bug the dropless
    dispatch removes (otherwise the regression tests above test nothing)."""
    cfg = _moe_cfg(moe_dispatch="capacity")
    p = M.moe_init(RNG, cfg)
    # overflow by construction: router column 0 reads a unit direction u
    # with gain 10, and every token carries exactly 3u plus noise
    # orthogonal to u, so expert 0's logit is 30 for every token while
    # |x . w_j| <= |x| |w_j| stays far below it for the random columns —
    # all 64 tokens pick expert 0 against a capacity of 40
    u = jnp.ones((cfg.d_model,), jnp.float32) / cfg.d_model ** 0.5
    p["router"]["w"] = p["router"]["w"].at[:, 0].set(10.0 * u)
    noise = jax.random.normal(jax.random.PRNGKey(3), (4, 16, cfg.d_model),
                              jnp.float32)
    x = noise - (noise @ u)[..., None] * u + 3.0 * u
    _, _, top_i = M._router(p, cfg, x.reshape(-1, cfg.d_model))
    assert bool(jnp.all(top_i[:, 0] == 0))
    assert 64 > M.capacity(64, cfg)
    full, _ = M.moe_apply(p, cfg, x)
    single = jnp.stack([M.moe_apply(p, cfg, x[b:b + 1, s:s + 1])[0][0, 0]
                        for b in range(4) for s in range(16)])
    assert float(jnp.max(jnp.abs(
        single.reshape(4, 16, -1) - full))) > 1e-4


def test_dropless_matches_capacity_when_nothing_drops():
    """With capacity >= every expert load the two dispatches compute the
    same function (post-drop renorm == row-local renorm when keep==all)."""
    cfg = _moe_cfg()
    p = M.moe_init(RNG, cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 4, cfg.d_model),
                          jnp.float32)  # the max(8, ...) capacity floor
    # gives capacity 8 >= any per-expert load at t=4 => nothing drops
    y_drop, _ = M.moe_apply(p, cfg, x)
    y_cap, _ = M.moe_apply(p, dataclasses.replace(cfg,
                                                  moe_dispatch="capacity"), x)
    np.testing.assert_allclose(np.asarray(y_drop), np.asarray(y_cap),
                               atol=2e-5)


# ------------------------------------------------- capacity renormalization

def test_capacity_renormalizes_over_kept_experts():
    """Applied combine weights sum to 1 over each row's *kept* experts (a
    row that loses an expert to the capacity limit redistributes, it does
    not silently under-weight the survivors); fully-dropped rows apply 0."""
    cfg = _moe_cfg(top_k=2, n_experts=4)
    t = 64
    # skewed routing: every row's first choice is expert 0 (load t=64 vs
    # capacity 40), second choice round-robins over the rest
    top_i = jnp.stack([jnp.zeros((t,), jnp.int32),
                       1 + jnp.arange(t, dtype=jnp.int32) % 3], axis=1)
    top_w = jnp.tile(jnp.array([[0.7, 0.3]], jnp.float32), (t, 1))
    _, st, _, keep, sw, c = M.capacity_route(cfg, top_w, top_i, t)
    assert c < t, "workload must overflow for this regression test"
    assert int(jnp.sum(~keep)) > 0, "no drops — capacity too large"
    applied = jnp.zeros((t,)).at[st].add(sw * keep.astype(jnp.float32))
    kept_per_row = jnp.zeros((t,), jnp.int32).at[st].add(keep.astype(jnp.int32))
    want = (kept_per_row > 0).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(applied), np.asarray(want),
                               atol=1e-6)


# ----------------------------------------------------------- fp32 combine

@pytest.mark.parametrize("dispatch", ["dropless", "capacity"])
def test_combine_accumulates_fp32(dispatch):
    """moe_apply matches a per-row fp32 oracle at fp32 tolerance: the
    combine (router weight x expert output, summed over k) accumulates in
    fp32 and casts to the model dtype once at the end."""
    cfg = _moe_cfg(moe_dispatch=dispatch, top_k=2, n_experts=4)
    p = M.moe_init(RNG, cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 4, cfg.d_model),
                          jnp.float32)  # small cohort: no capacity drops
    got, _ = M.moe_apply(p, cfg, x)
    xf = x.reshape(-1, cfg.d_model)
    _, top_w, top_i = M._router(p, cfg, xf)
    top_w = np.asarray(top_w / top_w.sum(-1, keepdims=True), np.float64)
    want = np.zeros(xf.shape, np.float64)
    for i in range(xf.shape[0]):
        for j in range(cfg.top_k):
            e = int(top_i[i, j])
            y = _loop_oracle(xf[i:i + 1], [0] * e + [1] +
                             [0] * (cfg.n_experts - e - 1),
                             p["w_gate"], p["w_in"], p["w_out"])
            want[i] += top_w[i, j] * y[0]
    np.testing.assert_allclose(np.asarray(got.reshape(want.shape)), want,
                               atol=5e-5)
    assert got.dtype == jnp.dtype(cfg.dtype)


# ------------------------------------------------------------- aux gating

def _scatter_adds(jaxpr):
    """All scatter-add output avals (shape, dtype) in a jaxpr, recursively."""
    found = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "scatter-add":
                a = eqn.outvars[0].aval
                found.append((tuple(a.shape), str(a.dtype)))
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    if hasattr(sub, "jaxpr"):   # ClosedJaxpr
                        walk(sub.jaxpr)
                    elif hasattr(sub, "eqns"):  # raw Jaxpr
                        walk(sub)

    walk(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)
    return found


def _aux_scatters(jaxpr, e):
    """The Switch aux loss is the only f32 (E,)-shaped scatter-add."""
    return [s for s in _scatter_adds(jaxpr) if s == ((e,), "float32")]


def test_moe_apply_aux_gating():
    cfg = _moe_cfg()
    p = M.moe_init(RNG, cfg)
    x = jnp.zeros((2, 3, cfg.d_model), jnp.float32)
    on = jax.make_jaxpr(lambda x: M.moe_apply(p, cfg, x))(x)
    off = jax.make_jaxpr(lambda x: M.moe_apply(p, cfg, x, want_aux=False))(x)
    assert len(_aux_scatters(on, cfg.n_experts)) == 1
    assert len(_aux_scatters(off, cfg.n_experts)) == 0


@pytest.mark.parametrize("arch", ["granite-moe-1b-a400m", "arctic-480b"])
def test_decode_trace_has_no_aux_work(arch):
    """The single-token decode step never computes the load-balance loss —
    it was dead work on every decode step before aux gating."""
    cfg = ARCHS[arch].reduced()
    p = init_params(RNG, cfg)
    batch = synth_batch(RNG, cfg, 8, 2, "prefill")
    _, caches = prefill(p, cfg, batch, max_len=12)
    tok = batch["tokens"][:, -1]
    jx = jax.make_jaxpr(
        lambda tok, caches: decode_step(p, cfg, tok, caches, jnp.int32(8)))(
        tok, caches)
    assert len(_aux_scatters(jx, cfg.n_experts)) == 0
    # sanity: the detector does see the aux scatter on the training forward
    from repro.models import forward
    jf = jax.make_jaxpr(lambda b: forward(p, cfg, b, remat=False))(
        {"tokens": batch["tokens"]})
    assert len(_aux_scatters(jf, cfg.n_experts)) > 0
