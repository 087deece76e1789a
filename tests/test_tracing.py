"""Program spans and the compile counter (core/tracing.py) on the tiny PPO
experiment: the spans read back from a profiler trace with their stats, on
the intervals the runtime and the executors promise, and the counter puts
each compile on the call that made it."""

import sys
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS
from repro.core import tracing
from repro.core.plan import Cluster
from repro.rlhf.experiment import ExperimentConfig, RLHFExperiment
from repro.rlhf.ppo import PPOHyperparameters

CALLS = ("actor_gen", "reward_inf", "ref_inf", "critic_inf", "actor_train",
         "critic_train")
PREFIXES = ("rt.", "ppo.")


@pytest.fixture(scope="module")
def exp():
    actor = ARCHS["qwen2-0.5b"].reduced()
    cfg = ExperimentConfig(batch=2, prompt_len=8, gen_len=4, search_iters=0,
                           ppo=PPOHyperparameters(n_minibatches=1))
    e = RLHFExperiment(actor, actor, Cluster(n_nodes=1, devs_per_node=1),
                       cfg, search=False)
    e.run_iteration(jax.random.PRNGKey(0))  # compiles everything once
    return e


def host_events(logdir) -> list:
    """(start_ns, end_ns, name, stats) of every host event in the trace."""
    from jax.profiler import ProfileData
    path = sorted(Path(logdir).rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name, dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def traced(exp, tmp_path_factory):
    """Host events of two warm iterations (1 and 2) run under the
    profiler, then a depth-2 ``run(steps=3)`` (iterations 3 to 5)."""
    logdir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(logdir)):
        for i in (1, 2):
            exp.run_iteration(jax.random.PRNGKey(i))
    two = host_events(logdir)
    logdir = tmp_path_factory.mktemp("trace_depth2")
    with jax.profiler.trace(str(logdir)):
        exp.engine.run(lambda t: {"prompts": exp.make_prompts(
            jax.random.PRNGKey(10 + t))}, steps=3, pipeline_depth=2)
    return two, host_events(logdir)


def spans(events, name):
    return [e for e in events if e[2] == name]


def test_one_exec_span_per_call_and_iteration(traced):
    events, _ = traced
    execs = spans(events, "rt.exec")
    got = sorted((e[3]["call"], e[3]["iteration"]) for e in execs)
    assert got == sorted((c, i) for c in CALLS for i in (1, 2))
    assert all(e[3]["attempt"] == 1 for e in execs)
    for name in ("rt.wait", "rt.realloc"):
        assert len(spans(events, name)) == len(CALLS) * 2, name
    retire = spans(events, "rt.retire")
    assert sorted(e[3]["iteration"] for e in retire) == [1, 2]
    # the PPO spans lie inside the train calls' executor spans
    for model in ("actor", "critic"):
        outer = [e for e in execs if e[3]["call"] == f"{model}_train"]
        for name in ("ppo.adv", "ppo.step", "ppo.sync"):
            inner = [e for e in spans(events, name)
                     if e[3]["model"] == model]
            assert len(inner) == 2, (model, name)
            for s, e, _, _ in inner:
                assert any(a <= s and e <= b for a, b, _, _ in outer), \
                    (model, name)
    # each iteration's calls lie inside its step event
    for s in spans(events, "rt.iteration"):
        mine = [e for e in execs if e[3]["iteration"] == s[3]["step_num"]]
        assert len(mine) == len(CALLS)
        assert all(s[0] <= e[0] and e[1] <= s[1] for e in mine)


def test_program_spans_never_take_the_benchmark_prefix(traced):
    for events in traced:
        names = {e[2] for e in events if e[2].startswith(PREFIXES)}
        assert names >= {"rt.iteration", "rt.exec", "ppo.adv"}
        assert not [e for e in events if e[2].startswith("call:")]


def test_depth_two_iterations_overlap(traced):
    _, events = traced
    its = sorted(spans(events, "rt.iteration"))
    assert [e[3]["step_num"] for e in its] == [3, 4, 5]
    # iteration t + 1 is admitted before iteration t retires
    assert all(its[i + 1][0] < its[i][1] for i in range(2))
    retire = sorted(e[3]["iteration"] for e in spans(events, "rt.retire"))
    assert retire == [3, 4, 5]


def test_warm_iteration_lowers_nothing(exp):
    n = len(exp.engine.records)
    outside = tracing.outside()
    exp.run_iteration(jax.random.PRNGKey(20))
    recs = {r.name: r for r in exp.engine.records[n:]}
    assert set(recs) == set(CALLS)
    # every call, the train calls' advantage estimate included, runs
    # programs compiled on the first iteration
    assert {n: r.lowerings for n, r in recs.items()} == dict.fromkeys(
        CALLS, 0)
    assert tracing.outside().lowerings == outside.lowerings
    calls = exp.engine.stats()["calls"]
    # the first iteration's compiles are on its calls' records
    assert all(calls[c]["lowerings"] >= 1
               for c in ("actor_train", "critic_train"))
    assert set(calls["(outside)"]) == {"traces", "lowerings", "compile_s"}


def test_each_thread_counts_into_its_own_record():
    """More threads than cores, each lowering programs of its own under its
    own record while the main thread lowers outside any: no count lands on
    another thread's record, and none is lost."""
    tracing.install()
    n_threads, per_thread = 16, 3
    recs = [tracing.Compiles() for _ in range(n_threads)]
    barrier = threading.Barrier(n_threads + 1, timeout=60)
    x = jnp.ones(3)  # made here: threads lower nothing but their own jits

    def lower(tag):
        for j in range(per_thread):
            # a fresh function each time: a new program to lower
            jax.jit(lambda x, j=j: x * (tag + j + 1.5))(x)

    def worker(i):
        with tracing.attribute(recs[i]):
            barrier.wait()
            lower(100 * i)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        before = tracing.outside()
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        barrier.wait()
        lower(-1)
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        after = tracing.outside()
    finally:
        sys.setswitchinterval(old)
    assert [r.lowerings for r in recs] == [per_thread] * n_threads
    assert after.lowerings - before.lowerings == per_thread


def test_counters_add_into_the_current_record_only():
    """``tracing.count`` adds trees into the record its thread made current
    with ``counting``, one tree per name, and does nothing outside one."""
    tracing.count("moe", {"held": jnp.ones(2, jnp.int32)})  # no record
    rec = {}
    with tracing.counting(rec):
        tracing.count("moe", {"held": jnp.array([1, 2])})
        tracing.count("moe", {"held": jnp.array([3, 4])})
        tracing.count("other", jnp.int32(5))
        tracing.count("other", jnp.int32(0))
        tracing.count("empty", {})  # a model without MoE counts nothing
        inner = {}
        with tracing.counting(inner):
            tracing.count("moe", {"held": jnp.array([9, 9])})
        tracing.count("moe", {"held": jnp.array([0, 1])})
    assert rec["moe"]["held"].tolist() == [4, 7]
    assert int(rec["other"]) == 5 and "empty" not in rec
    assert inner["moe"]["held"].tolist() == [9, 9]
    total = {"moe": {"held": jnp.array([1, 1])}}
    tracing.add_counts(total, rec)
    assert total["moe"]["held"].tolist() == [5, 8] and "other" in total
