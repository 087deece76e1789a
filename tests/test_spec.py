"""Speculative draft-and-verify rollout: exactness (greedy bit-parity,
rejection-sampling distribution, PPO logprob bookkeeping), allocator
grow/truncate invariants, the adaptive draft-length controller, plan /
estimator / verifier integration, and the serve-path spec mode.

The exactness tests deliberately use a *noise-perturbed* draft: tiny
random-init models are near-deterministic (every head emits one repeated
token), so an unperturbed draft degenerately agrees with the target and
the rejection path never runs.  The perturbed draft disagrees almost
everywhere — parity then proves correction/truncation, not luck."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.configs.llama import LLAMA_7B, critic_of
from repro.kernels import ops
from repro.models import model as MDL
from repro.models import spec
from repro.models.paged_cache import BlockAllocator, needed_blocks

RNG = jax.random.PRNGKey(0)


def _noisy(params, scale=0.5, seed=7):
    k = jax.random.PRNGKey(seed)
    return jax.tree_util.tree_map(
        lambda l: l + scale * jax.random.normal(k, l.shape, l.dtype)
        if jnp.issubdtype(l.dtype, jnp.floating) else l, params)


# ------------------------------------------------- BucketedGenerator cache

def test_bucketed_generator_cache_keys_sampling_attrs():
    """Regression: the jit cache key must include every mutable sampling
    attribute the compiled fn closes over (sampler/top_k/top_p/eos_id/...);
    a stale hit would silently decode with the old settings."""
    cfg = ARCHS["qwen2-0.5b"].reduced()
    params = MDL.init_params(RNG, cfg)
    # a flat, high-temperature draw over 16 steps x 8 rows against the
    # greedy (top_k=1) stream: equal streams would need 128 draws from a
    # near-uniform distribution over the vocab to hit the argmax each time
    gen = MDL.BucketedGenerator(cfg, temperature=100.0)
    # a bucket-sized prompt is not left-padded, so the generator's greedy
    # stream is the one ``generate`` gives on the same prompt
    batch = MDL.synth_batch(jax.random.PRNGKey(1), cfg, 16, 2, "prompt")
    out0 = gen(params, batch, num_new_tokens=16, rng=jax.random.PRNGKey(2))
    assert gen.compiles == 1

    gen.top_k = 1  # greedy-equivalent truncation: observably different
    out1 = gen(params, batch, num_new_tokens=16, rng=jax.random.PRNGKey(2))
    assert gen.compiles == 2, "top_k change must miss the jit cache"
    g = MDL.generate(params, cfg, batch, num_new_tokens=16, rng=None)
    np.testing.assert_array_equal(np.asarray(out1["tokens"]),
                                  np.asarray(g["tokens"]))
    assert not np.array_equal(np.asarray(out0["tokens"]),
                              np.asarray(out1["tokens"]))

    gen.sampler = "gumbel"
    gen(params, batch, num_new_tokens=16, rng=jax.random.PRNGKey(2))
    assert gen.compiles == 3, "sampler change must miss the jit cache"
    gen.eos_id = 3
    gen(params, batch, num_new_tokens=16, rng=jax.random.PRNGKey(2))
    assert gen.compiles == 4, "eos_id change must miss the jit cache"
    gen.top_p = 0.9
    gen(params, batch, num_new_tokens=16, rng=jax.random.PRNGKey(2))
    assert gen.compiles == 5, "top_p change must miss the jit cache"

    hits = gen.hits
    gen.top_k, gen.sampler, gen.eos_id, gen.top_p = 1, "cdf", None, 1.0
    gen(params, batch, num_new_tokens=16, rng=jax.random.PRNGKey(2))
    assert gen.compiles == 5 and gen.hits == hits + 1  # old key still cached


# ------------------------------------------------- allocator grow/truncate

def test_truncate_to_invariants():
    a = BlockAllocator(10, block_size=4)
    blocks = a.alloc(5)
    kept = a.truncate_to(blocks, 9)  # needs ceil(9/4)=3
    assert kept == blocks[:3] and len(blocks) == 5  # input not mutated
    assert a.used_count == 3 and a.free_count == 6
    with pytest.raises(ValueError):
        a.truncate_to(kept, 13)  # would need 4 > owned 3
    assert a.used_count == 3  # refused call freed nothing
    assert a.truncate_to(kept, 12) == kept  # exact fit keeps everything
    empty = a.truncate_to(kept, 0)
    assert empty == [] and a.used_count == 0 and a.free_count == 9
    with pytest.raises(ValueError):
        a.truncate_to(kept, 1)  # stale list: blocks already freed


def test_truncate_grow_cycles_conserve_pool():
    """Speculative lifecycle fuzz: rows repeatedly grow to cover a verify
    window then truncate to the committed length; the pool never leaks and
    ownership always matches needed_blocks."""
    bs, rows = 4, 3
    a = BlockAllocator(64, block_size=bs)
    rng = np.random.default_rng(0)
    blocks = [a.alloc(1) for _ in range(rows)]
    lens = [1] * rows
    for _ in range(50):
        i = int(rng.integers(rows))
        k = int(rng.integers(1, 6))
        while needed_blocks(lens[i] + k + 1, bs) > len(blocks[i]):
            blocks[i] = blocks[i] + a.alloc(1)
        lens[i] += int(rng.integers(0, k + 2))  # commit r+1 in [0, k+1]
        if needed_blocks(lens[i], bs) < len(blocks[i]):
            blocks[i] = a.truncate_to(blocks[i], lens[i])
        assert len(blocks[i]) >= needed_blocks(lens[i], bs)
        assert a.used_count == sum(len(b) for b in blocks)
        flat = [x for b in blocks for x in b]
        assert len(flat) == len(set(flat))  # no block owned twice
    for i in range(rows):
        blocks[i] = a.truncate_to(blocks[i], 0)
    assert a.used_count == 0


# ------------------------------------------------------- support predicate

def test_spec_supported_and_pair_check():
    qwen = ARCHS["qwen2-0.5b"].reduced()
    assert spec.spec_supported(qwen)
    assert not spec.spec_supported(ARCHS["mamba2-1.3b"].reduced())
    spec.check_spec_pair(qwen, qwen)  # self-pair fine
    with pytest.raises(ValueError, match="vocab"):
        spec.check_spec_pair(qwen, dataclasses.replace(qwen, vocab_size=77))
    with pytest.raises(ValueError):
        spec.check_spec_pair(qwen, ARCHS["mamba2-1.3b"].reduced())


# --------------------------------------------------------------- exactness

@pytest.mark.parametrize("arch", ["qwen2-0.5b", "gemma3-1b",
                                  "granite-moe-1b-a400m"])
def test_spec_greedy_bit_parity(arch):
    """Greedy spec decode == plain generate, bit for bit, for an
    adversarial (noise-perturbed) draft — dense, windowed, and MoE."""
    cfg = ARCHS[arch].reduced()
    params = MDL.init_params(RNG, cfg)
    batch = MDL.synth_batch(jax.random.PRNGKey(1), cfg, 6, 2, "prompt")
    ref = MDL.generate(params, cfg, batch, num_new_tokens=8, rng=None)
    out = spec.spec_generate(params, cfg, _noisy(params), cfg, batch,
                             num_new_tokens=8, spec_k=3, rng=None)
    np.testing.assert_array_equal(np.asarray(ref["tokens"]),
                                  np.asarray(out["tokens"]))
    np.testing.assert_allclose(np.asarray(ref["logprobs"]),
                               np.asarray(out["logprobs"]), atol=2e-4)
    # the adversarial draft must actually exercise the rejection path
    assert out["stats"]["accept_rate"] < 0.5


def test_spec_verify_rejection_sampling_distribution():
    """Seeded statistical check of the rejection-sampling invariant: over
    many independent verify trials with a disagreeing draft, the first
    emitted token's empirical marginal matches the target's sampling
    distribution."""
    n, k, v = 4000, 2, 8
    kp, kq, kk = jax.random.split(jax.random.PRNGKey(5), 3)
    p_log = jax.random.normal(kp, (1, k + 1, v)) * 1.5
    q_log = jax.random.normal(kq, (1, k, v)) * 1.5
    # draft proposes from q (greedy-ish spread): sample per trial from q
    q0 = jax.nn.softmax(q_log[0, 0])
    draft0 = jax.random.categorical(kk, jnp.log(q0), shape=(n,))
    draft = jnp.stack([draft0, jnp.zeros((n,), jnp.int32)], axis=1)
    acc, tok, _, _ = ops.spec_verify(
        jnp.tile(p_log, (n, 1, 1)), draft.astype(jnp.int32),
        jnp.tile(q_log, (n, 1, 1)), key=jax.random.PRNGKey(11))
    acc, tok, draft0 = (np.asarray(acc), np.asarray(tok), np.asarray(draft0))
    first = np.where(acc >= 1, draft0, tok)
    emp = np.bincount(first, minlength=v) / n
    tgt = np.asarray(jax.nn.softmax(p_log[0, 0]))
    assert 0 < acc.min() or acc.max() >= 1  # both branches exercised
    np.testing.assert_allclose(emp, tgt, atol=0.04)


def test_spec_logprobs_match_teacher_forced_target():
    """Sampled spec rollout logprobs == full-distribution log_softmax of a
    teacher-forced target forward at the same positions (PPO convention:
    untempered target, regardless of draft/k/accept pattern)."""
    cfg = ARCHS["qwen2-0.5b"].reduced()
    params = MDL.init_params(RNG, cfg)
    batch = MDL.synth_batch(jax.random.PRNGKey(1), cfg, 6, 2, "prompt")
    out = spec.spec_generate(params, cfg, _noisy(params), cfg, batch,
                             num_new_tokens=8, spec_k=3,
                             rng=jax.random.PRNGKey(9), temperature=0.8,
                             top_k=16)
    toks = np.asarray(out["tokens"])
    full = jnp.concatenate([batch["tokens"], jnp.asarray(toks)], axis=1)
    hidden, *_ = MDL.forward(params, cfg, {"tokens": full}, remat=False)
    logits = MDL.logits_of(params, cfg, hidden)
    lps = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    p = batch["tokens"].shape[1]
    want = jnp.take_along_axis(lps[:, p - 1:-1],
                               jnp.asarray(toks)[:, :, None], axis=-1)[..., 0]
    np.testing.assert_allclose(np.asarray(out["logprobs"]),
                               np.asarray(want), atol=2e-4)


# -------------------------------------------------------------- controller

def test_spec_controller_adapts_k_to_accept_rate():
    hi, lo = spec.SpecController(), spec.SpecController()
    for _ in range(20):
        hi.update(0.95)
        lo.update(0.1)
    assert hi.k > lo.k
    assert lo.k == lo.k_min
    assert hi.k >= 4  # high accept pushes toward long drafts
    # expectation endpoints
    assert spec.SpecController.expected_committed(0.0, 5) == 1.0
    assert spec.SpecController.expected_committed(0.999999, 5) == \
        pytest.approx(6.0, rel=1e-4)
    with pytest.raises(ValueError):
        spec.SpecController(k_min=3, init_k=2)


# --------------------------------------------------- plan/verifier/costing

def test_build_ppo_draft_graph_and_verifier_rule():
    from repro.analysis.verify import verify_graph
    from repro.core.dfg import GENERATE, DataflowGraph, build_ppo

    draft = dataclasses.replace(LLAMA_7B, name="llama-draft", num_layers=8,
                                n_superblocks=8)
    g = build_ppo(LLAMA_7B, critic_of(LLAMA_7B), batch=64, prompt_len=128,
                  gen_len=128, draft=draft)
    dg = g.by_name["draft_gen"]
    assert dg.call_type == GENERATE and dg.outputs == ("draft_seq",)
    assert "draft_seq" in g.by_name["actor_gen"].inputs
    assert not [d for d in verify_graph(g) if d.severity == "error"]

    # vocab mismatch and recurrent drafts are static errors
    bad_vocab = dataclasses.replace(draft, vocab_size=1000)
    g2 = build_ppo(LLAMA_7B, critic_of(LLAMA_7B), batch=64, prompt_len=128,
                   gen_len=128, draft=bad_vocab)
    errs = [d for d in verify_graph(g2) if d.rule == "spec-draft"]
    assert errs and all(d.severity == "error" for d in errs)
    mamba = ARCHS["mamba2-1.3b"]
    g3 = build_ppo(LLAMA_7B, critic_of(LLAMA_7B), batch=64, prompt_len=128,
                   gen_len=128,
                   draft=dataclasses.replace(mamba,
                                             vocab_size=LLAMA_7B.vocab_size))
    assert [d for d in verify_graph(g3) if d.rule == "spec-draft"]
    assert isinstance(g3, DataflowGraph)


def test_estimator_spec_costing():
    from repro import hw
    from repro.core.dfg import build_ppo
    from repro.core.estimator import CostModel, spec_expected_committed
    from repro.core.plan import (Assignment, Cluster, DeviceMesh,
                                 ParallelStrategy)

    # truncated-geometric expectation: monotone in both arguments
    assert spec_expected_committed(0.0, 4) == 1.0
    assert spec_expected_committed(0.9, 4) > spec_expected_committed(0.5, 4)
    assert spec_expected_committed(0.9, 6) > spec_expected_committed(0.9, 2)

    cluster = Cluster(n_nodes=2, devs_per_node=8, chip=hw.H100,
                      intra_node_bw=450e9, inter_node_bw=50e9)
    cost = CostModel(cluster)
    draft = dataclasses.replace(LLAMA_7B, name="llama-draft", num_layers=8,
                                n_superblocks=8)
    g = build_ppo(LLAMA_7B, critic_of(LLAMA_7B), batch=64, prompt_len=512,
                  gen_len=512, draft=draft)
    call = g.by_name["actor_gen"]
    asg = Assignment(DeviceMesh(0, 1, 0, 8), ParallelStrategy(2, 4, 1, 8))

    # verify's bandwidth amortization: k+1 positions cost far less than
    # k+1 single-position dispatches while decode is memory-bound
    t1 = cost.decode_step_time(LLAMA_7B, 64, 768, asg)
    t5 = cost.decode_step_time(LLAMA_7B, 64, 768, asg, n_positions=5)
    assert t1 < t5 < 5 * t1

    # a cheap draft at a decent accept rate beats plain decode, and the
    # optimal k grows with the accept rate
    t_plain = cost.call_time(call, asg)
    t_spec = cost.spec_generate_time(call, asg, draft, asg, k=4,
                                     accept_rate=0.8)
    assert t_spec < t_plain
    k_lo = cost.optimal_spec_k(call, asg, draft, asg, accept_rate=0.05)
    k_hi = cost.optimal_spec_k(call, asg, draft, asg, accept_rate=0.95)
    assert k_lo < k_hi

    # measured-rate EMA feeds the same knob
    cost.record_accept_rate("actor", 1.0)
    assert cost.accept_rate("actor") > 0.7 == cost.accept_rate("other")


# ------------------------------------------------------- experiment + serve

def test_experiment_spec_rollout_end_to_end():
    """ExperimentConfig.draft_model: a full PPO iteration rolls out through
    spec_generate, reports spec stats, feeds the accept EMA back into the
    cost model, and never updates the frozen draft."""
    from repro.core.plan import Cluster
    from repro.rlhf import ppo as PPO
    from repro.rlhf.experiment import ExperimentConfig, RLHFExperiment

    actor = ARCHS["qwen2-0.5b"].reduced()
    draft = dataclasses.replace(
        actor, name=actor.name + "-draft", num_layers=1, n_superblocks=1)
    exp = ExperimentConfig(batch=2, prompt_len=8, gen_len=8,
                           draft_model=draft, spec_k=3,
                           ppo=PPO.PPOHyperparameters(n_minibatches=2))
    e = RLHFExperiment(actor, actor, Cluster(n_nodes=1, devs_per_node=1),
                       exp, search=False)
    assert "draft_gen" in e.graph.by_name
    d0 = jax.tree.map(np.asarray, e.models["draft"].params)
    out = e.run_iteration(jax.random.PRNGKey(0))
    assert np.isfinite(out["actor_stats"]["loss"])
    st = out["spec_stats"]
    assert st["proposed"] > 0 and 0.0 <= st["accept_rate"] <= 1.0
    assert e.cost.accept_rate("actor", default=-1.0) >= 0.0
    for a, b in zip(jax.tree.leaves(e.models["draft"].params),
                    jax.tree.leaves(d0)):
        np.testing.assert_array_equal(np.asarray(a), b)


def test_experiment_spec_rejects_bad_pairs():
    from repro.core.plan import Cluster
    from repro.rlhf.experiment import ExperimentConfig, RLHFExperiment

    actor = ARCHS["qwen2-0.5b"].reduced()
    exp = ExperimentConfig(batch=2, prompt_len=8, gen_len=8,
                           draft_model=dataclasses.replace(actor,
                                                           vocab_size=99))
    with pytest.raises(ValueError, match="vocab"):
        RLHFExperiment(actor, actor, Cluster(n_nodes=1, devs_per_node=1),
                       exp, search=False)


def test_serve_spec_mode_greedy_parity_and_stats():
    from repro.launch.serve import ContinuousBatchServer
    from repro.models import init_params

    cfg = ARCHS["qwen2-0.5b"].reduced()
    params = init_params(RNG, cfg)
    rng = np.random.default_rng(0)
    prompts = [np.asarray(rng.integers(1, cfg.vocab_size, int(n)), np.int32)
               for n in (5, 11, 7, 6)]

    def run(**kw):
        srv = ContinuousBatchServer(cfg, params, n_slots=2, max_prompt=16,
                                    max_new=8, temperature=0.0, **kw)
        return srv, *srv.serve(prompts)

    _, pt, _ = run()
    srv, st_toks, _ = run(draft_params=_noisy(params), draft_cfg=cfg,
                          spec_k=3,
                          spec_controller=spec.SpecController(init_k=3))
    for a, b in zip(pt, st_toks):
        np.testing.assert_array_equal(a, b)
    st = srv.stats()
    assert st["spec_cycles"] > 0 and st["spec_proposed"] > 0
    assert st["spec_accept_rate"] < 0.5  # adversarial draft
    assert len(st["spec_k_trace"]) == st["spec_cycles"]
    assert st["latency_s"]["n"] == len(prompts)
    assert st["latency_s"]["p50"] <= st["latency_s"]["p99"]

    with pytest.raises(ValueError, match="together"):
        ContinuousBatchServer(cfg, params, draft_params=params)
