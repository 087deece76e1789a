"""Chaos benchmark: kill a host mid-iteration and measure recovery.

Runs a PPO-shaped toy graph on a 2-node x 2-device logical cluster (4 forced
host devices, so reshards are genuine multi-device collectives) with a
deterministic ``FaultInjector`` killing node 1 in the middle of an
iteration, and measures the two recovery paths of the elastic runtime:

  live        — the actor generates data-parallel on the full mesh, so a
                complete replica survives the loss: recovery = replan on the
                survivor topology + live weight reshard through
                ``parallel/realloc_exec`` (no disk touched)
  checkpoint  — the actor is pinned entirely to the killed node, so every
                replica dies: recovery falls back to ``CheckpointManager``
                restore of the last retired step, then reshards onto the
                survivor plan

Both scenarios replay only the calls that had not completed (carried
done-set), and the benchmark asserts the post-recovery weights are
bit-identical to an uninterrupted run of the same length — the train
updates are order-sensitive, so this checks exactly-once TRAIN semantics,
not just convergence.  The live path runs at pipeline depth 1 and 2; the
checkpoint path at depth 1 (a retirement-time snapshot is only exact when
no later train step may already have run).

Reported recovery times come from ``engine.recoveries[0]`` (replan +
restore + reshard + bookkeeping, measured inside the engine).

Two graceful-degradation scenarios ride on the same toy
(``--scenario preempt`` / ``--scenario straggler``):

  preempt   — a scripted preemption *notice* for node 1 instead of a kill:
              the engine migrates (replan avoiding the doomed host, live
              drain, retire at a safe point) with zero aborted calls and
              zero checkpoint restores, and the benchmark asserts the
              migrate recovery work is strictly cheaper than the reactive
              live path above, at bit-identical weights
  straggler — a scripted delay stalls one inference call far past its
              deadline; with ``speculative_redispatch`` the engine races a
              duplicate on the idle node and the first finisher wins.  The
              benchmark asserts the speculative run beats the
              no-speculation baseline wall clock, TRAIN calls ran exactly
              once (never duplicated), and weights stay bit-identical

The toy's trainable models carry optimizer-moment trees (the train update
folds the moment into the weights), so opt-state recovery errors are
observable as weight divergence, not just metadata drift.  Wired into
``benchmarks/run.py`` as ``--only chaos``; CI runs each scenario with
``--smoke --json`` and uploads the JSON artifacts.
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

DEVS_PER_NODE = 2
N_NODES = 2


def _toy(*, actor_on="full", dim=512, n_leaves=8, sleep_s=0.01):
    """Build (dfg, plan, models, sharding_for, executors, replanner).

    Deterministic, placement-independent train updates through an
    optimizer-moment tree (m -> m*0.9 + r; x -> x*0.5 + m): final weights
    are an exact function of the retired call sequence AND the recovered
    moments, so comparing against an uninterrupted run is a strict replay
    check that also catches stale/corrupted opt state.
    ``actor_on="full"`` generates dp=4 on the full mesh (a replica survives
    any single-host loss); ``actor_on="node1"`` pins the actor to node 1
    (the node the injector kills) so every replica dies.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core.dfg import (DataflowGraph, FunctionCall, GENERATE,
                                INFERENCE, TRAIN, Workload)
    from repro.core.plan import (Assignment, Cluster, DeviceMesh,
                                 ExecutionPlan, ParallelStrategy)
    from repro.core.runtime import ModelState

    cluster = Cluster(n_nodes=N_NODES, devs_per_node=DEVS_PER_NODE)
    w = Workload(batch=4, prompt_len=8, gen_len=8)
    calls = [
        FunctionCall("gen", "actor", GENERATE, None, w,
                     ("prompts",), ("seq",), trainable=True),
        FunctionCall("rew", "reward", INFERENCE, None, w,
                     ("seq",), ("r",)),
        FunctionCall("atrain", "actor", TRAIN, None, w,
                     ("r",), ("a_out",), trainable=True),
        FunctionCall("ctrain", "critic", TRAIN, None, w,
                     ("r",), ("c_out",), trainable=True),
    ]
    dfg = DataflowGraph(calls, "chaos-toy")
    node0 = DeviceMesh(0, 1, 0, DEVS_PER_NODE)
    node1 = DeviceMesh(1, 1, 0, DEVS_PER_NODE)
    full = cluster.full_mesh()
    if actor_on == "full":
        gen_asg = Assignment(full, ParallelStrategy(full.size, 1, 1, 1))
        atrain_asg = Assignment(node0, ParallelStrategy(1, DEVS_PER_NODE,
                                                        1, 1))
    else:  # pinned to the doomed node: checkpoint-fallback scenario
        gen_asg = Assignment(node1, ParallelStrategy(DEVS_PER_NODE, 1, 1, 1))
        atrain_asg = Assignment(node1, ParallelStrategy(1, DEVS_PER_NODE,
                                                        1, 1))
    plan = ExecutionPlan({
        "gen": gen_asg,
        "rew": Assignment(node1, ParallelStrategy(DEVS_PER_NODE, 1, 1, 1)),
        "atrain": atrain_asg,
        "ctrain": Assignment(node0, ParallelStrategy(DEVS_PER_NODE, 1, 1, 1)),
    }, cluster)

    # logical device id -> physical jax device.  The replanner trims this
    # when a node dies, so post-recovery shardings land on the survivors.
    devs = list(jax.devices())
    multi = len(devs) >= N_NODES * DEVS_PER_NODE
    state = {"phys": devs[:N_NODES * DEVS_PER_NODE] if multi else devs}
    single = NamedSharding(Mesh(np.array(devs[:1]), ("x",)), P())

    def sharding_for(model_name, asg):
        if model_name not in ("actor", "critic"):
            return None
        if not multi:  # degraded in-process fallback: pure aliases
            return {f"w{i}": single for i in range(n_leaves)}
        ids = sorted(asg.mesh.devices(DEVS_PER_NODE))
        mesh = Mesh(np.array([state["phys"][d] for d in ids]), ("x",))
        spec = (P("x", None) if asg.strategy.tp > 1
                and dim % asg.strategy.tp == 0 else P())
        sh = NamedSharding(mesh, spec)
        return {f"w{i}": sh for i in range(n_leaves)}

    def replanner(new_cluster, event):
        if event.kind == "loss" and multi:
            dead = {d for n in event.nodes
                    for d in range(n * DEVS_PER_NODE,
                                   (n + 1) * DEVS_PER_NODE)}
            state["phys"] = [p for i, p in enumerate(state["phys"])
                             if i not in dead]
        if event.kind == "notice":
            # preemption: SAME cluster (the doomed host is still up and
            # draining — no renumbering), everything planned off of it.
            # The toy only ever notices node 1, so node 0 survives.
            mesh = DeviceMesh(0, 1, 0, DEVS_PER_NODE)
            n = mesh.size
            dp = Assignment(mesh, ParallelStrategy(n, 1, 1, 1))
            tp = Assignment(mesh, ParallelStrategy(1, n, 1, 1))
            return ExecutionPlan({"gen": dp, "rew": dp, "atrain": tp,
                                  "ctrain": dp}, new_cluster)
        nfull = new_cluster.full_mesh()
        n = nfull.size
        dp = Assignment(nfull, ParallelStrategy(n, 1, 1, 1))
        tp = Assignment(nfull, ParallelStrategy(1, n, 1, 1))
        return ExecutionPlan({"gen": dp, "rew": dp, "atrain": tp,
                              "ctrain": dp}, new_cluster)

    # opt-moment trees mirror the param keys, so ``sharding_for`` doubles
    # as the engine's ``opt_sharding_for``
    models = {
        "actor": ModelState({f"w{i}": jnp.full((dim, dim), float(i + 1),
                                               jnp.float32)
                             for i in range(n_leaves)},
                            {f"w{i}": jnp.zeros((dim, dim), jnp.float32)
                             for i in range(n_leaves)}),
        "reward": ModelState({}),
        "critic": ModelState({f"w{i}": jnp.full((dim, dim), 2.0,
                                                jnp.float32)
                              for i in range(n_leaves)},
                             {f"w{i}": jnp.zeros((dim, dim), jnp.float32)
                              for i in range(n_leaves)}),
    }

    def gen(ms, inputs):
        time.sleep(sleep_s)
        return {"seq": inputs["prompts"]}

    def rew(ms, inputs):
        time.sleep(sleep_s)
        return {"r": 2 * inputs["seq"] + 1}

    def mk_train(out_key):
        def train(ms, inputs):
            import jax as _jax
            time.sleep(sleep_s)
            r = float(inputs["r"])
            # moment update folds into the weights: stale or lost moments
            # corrupt the weights observably, not just silently
            ms.opt_state = _jax.tree.map(lambda m: m * 0.9 + r,
                                         ms.opt_state)
            ms.params = _jax.tree.map(lambda x, m: x * 0.5 + m,
                                      ms.params, ms.opt_state)
            return {out_key: r}
        return train

    executors = {"gen": gen, "rew": rew, "atrain": mk_train("a_out"),
                 "ctrain": mk_train("c_out")}
    return dfg, plan, models, sharding_for, executors, replanner


def _leaves(ms):
    import jax
    import numpy as np
    # params AND opt moments: identity must cover the full trainable state
    return [np.asarray(jax.device_get(x))
            for x in jax.tree.leaves((ms.params, ms.opt_state))]


def _reference(steps, **kw):
    from repro.core.runtime import RuntimeEngine
    dfg, plan, models, sharding_for, executors, _rp = _toy(**kw)
    eng = RuntimeEngine(dfg, plan, executors, models,
                        sharding_for=sharding_for,
                        opt_sharding_for=sharding_for)
    eng.run(lambda t: {"prompts": t}, steps=steps)
    return _leaves(models["actor"]), _leaves(models["critic"])


def _identical(models, ref):
    import numpy as np
    ref_a, ref_c = ref
    got_a, got_c = _leaves(models["actor"]), _leaves(models["critic"])
    return (all(np.array_equal(g, w) for g, w in zip(got_a, ref_a))
            and all(np.array_equal(g, w) for g, w in zip(got_c, ref_c)))


def _run_scenario(*, mode, depth, steps, kill_iter, dim, n_leaves, sleep_s,
                  ckpt_dir=None):
    """Kill node 1 at ``rew@kill_iter``, recover, and report the engine's
    recovery record plus the bit-identity verdict."""
    from repro.checkpoint.manager import CheckpointManager
    from repro.core import fault as FLT
    from repro.core.runtime import RuntimeEngine

    kw = {"actor_on": "full" if mode == "live" else "node1",
          "dim": dim, "n_leaves": n_leaves, "sleep_s": sleep_s}
    ref = _reference(steps, **kw)
    dfg, plan, models, sharding_for, executors, replanner = _toy(**kw)
    inj = FLT.FaultInjector().kill_host(1, at_call="rew",
                                        at_iteration=kill_iter)
    eng = RuntimeEngine(dfg, plan, executors, models,
                        sharding_for=sharding_for,
                        opt_sharding_for=sharding_for,
                        fault_injector=inj, replanner=replanner)
    on_retire = None
    if mode == "checkpoint":
        ckpt = CheckpointManager(ckpt_dir, keep=3)

        def on_retire(t, pool):
            ckpt.save(t, {"actor": models["actor"].params,
                          "critic": models["critic"].params,
                          "actor_opt": models["actor"].opt_state,
                          "critic_opt": models["critic"].opt_state})

        def restore(lost):
            ckpt.wait()
            template = {n: models[n].params for n in lost}
            template.update({f"{n}_opt": models[n].opt_state for n in lost})
            _s, trees, _x = ckpt.restore(template)
            for n in lost:
                models[n].params = trees[n]
                models[n].opt_state = trees[f"{n}_opt"]

        eng.restore_models = restore
    t0 = time.monotonic()
    eng.run(lambda t: {"prompts": t}, steps=steps,
            pipeline_depth=depth, on_retire=on_retire)
    wall_s = time.monotonic() - t0
    assert len(eng.recoveries) == 1, eng.recoveries
    rec = dict(eng.recoveries[0])
    assert rec["mode"] == mode, (mode, rec)
    return {
        "mode": rec["mode"],
        "pipeline_depth": depth,
        "killed_at": f"rew@{kill_iter}",
        "recovery_s": rec["total_s"],
        "replan_s": rec["replan_s"],
        "restore_s": rec["restore_s"],
        "reshard_s": rec["reshard_s"],
        "moved_bytes": rec["moved_bytes"],
        "lost_models": rec["lost_models"],
        "surviving_devices": rec["surviving_devices"],
        "resumed_iteration": rec["resumed_iteration"],
        "opt_state_resharded_bytes": eng.opt_state_resharded_bytes,
        "bit_identical": _identical(models, ref),
        "run_wall_s": wall_s,
    }


def _run_preempt(*, steps, notice_iter, deadline_s, dim, n_leaves, sleep_s,
                 depth=1):
    """Notice node 1 at ``rew@notice_iter`` with a generous deadline: the
    engine must migrate — zero aborted calls, zero checkpoint restores —
    and finish bit-identical to the uninterrupted run."""
    from repro.core import fault as FLT
    from repro.core.runtime import RuntimeEngine

    kw = {"actor_on": "full", "dim": dim, "n_leaves": n_leaves,
          "sleep_s": sleep_s}
    ref = _reference(steps, **kw)
    dfg, plan, models, sharding_for, executors, replanner = _toy(**kw)
    inj = FLT.FaultInjector().notice(1, deadline_s, at_call="rew",
                                     at_iteration=notice_iter)
    eng = RuntimeEngine(dfg, plan, executors, models,
                        sharding_for=sharding_for,
                        opt_sharding_for=sharding_for,
                        fault_injector=inj, replanner=replanner)
    t0 = time.monotonic()
    eng.run(lambda t: {"prompts": t}, steps=steps, pipeline_depth=depth)
    wall_s = time.monotonic() - t0
    stats = eng.stats()
    assert eng.aborted_calls == 0, eng.aborted_calls
    assert len(eng.recoveries) == 1, eng.recoveries
    rec = dict(eng.recoveries[0])
    assert rec["mode"] == "migrate", rec
    assert rec["restore_s"] == 0.0 and not rec["lost_models"], rec
    assert stats["preemption_migrations"] == 1, stats
    return {
        "mode": "migrate",
        "pipeline_depth": depth,
        "noticed_at": f"rew@{notice_iter}",
        "deadline_s": deadline_s,
        "recovery_s": rec["total_s"],
        "drain_s": rec["drain_s"],
        "replan_s": rec["replan_s"],
        "reshard_s": rec["reshard_s"],
        "moved_bytes": rec["moved_bytes"],
        "aborted_calls": eng.aborted_calls,
        "checkpoint_restores": 0,
        "bit_identical": _identical(models, ref),
        "run_wall_s": wall_s,
    }


def _run_straggler(*, speculate, steps, delay_iter, delay_s, dim, n_leaves,
                   sleep_s, base_s=0.05, factor=2.0):
    """Stall ``rew@delay_iter`` for ``delay_s`` (far past its deadline
    ``factor * base_s``); with ``speculate`` the engine races a duplicate
    on the idle node.  TRAIN calls must run exactly once either way."""
    from repro.core import fault as FLT
    from repro.core.dfg import TRAIN, base_name
    from repro.core.runtime import RuntimeEngine

    class _FlatCost:
        """Deadline source only: the toy calls have no ModelConfig, so the
        analytic estimator can't price them."""

        def __init__(self, base):
            self.base = base

        def call_time(self, call, asg):
            return self.base

    kw = {"actor_on": "full", "dim": dim, "n_leaves": n_leaves,
          "sleep_s": sleep_s}
    ref = _reference(steps, **kw)
    dfg, plan, models, sharding_for, executors, replanner = _toy(**kw)
    inj = FLT.FaultInjector().delay_call("rew", seconds=delay_s,
                                         at_iteration=delay_iter)
    eng = RuntimeEngine(dfg, plan, executors, models,
                        sharding_for=sharding_for,
                        opt_sharding_for=sharding_for,
                        cost_model=_FlatCost(base_s),
                        straggler_factor=factor,
                        fault_injector=inj,
                        speculative_redispatch=speculate)
    t0 = time.monotonic()
    eng.run(lambda t: {"prompts": t}, steps=steps)
    wall_s = time.monotonic() - t0
    stats = eng.stats()
    # exactly-once TRAIN: never duplicated, one record per iteration
    train_counts: dict[str, int] = {}
    for r in eng.records:
        call = dfg.by_name[base_name(r.name)]
        if call.call_type == TRAIN:
            assert not r.speculated, r
            train_counts[call.name] = train_counts.get(call.name, 0) + 1
    assert all(n == steps for n in train_counts.values()), train_counts
    return {
        "speculative_redispatch": speculate,
        "delayed_at": f"rew@{delay_iter}",
        "delay_s": delay_s,
        "deadline_s": base_s * factor,
        "wall_s": wall_s,
        "stragglers": stats["stragglers"],
        "speculative_dispatches": stats["speculative_dispatches"],
        "speculative_wins": stats["speculative_wins"],
        "bit_identical": _identical(models, ref),
    }


def bench_chaos(steps=6, kill_iter=2, dim=512, n_leaves=8, sleep_s=0.01,
                work_dir=None):
    """Returns (csv_rows, json_summary)."""
    import jax
    # warm-up: the first reshard of a given shape pays JAX dispatch/compile
    # warm-up that would otherwise be billed to whichever scenario runs
    # first; run one throwaway recovery so the measured ones are warm-vs-warm
    _run_scenario(mode="live", depth=1, steps=3, kill_iter=1, dim=dim,
                  n_leaves=n_leaves, sleep_s=0.0)
    if work_dir is None:
        work_dir = tempfile.mkdtemp(prefix="chaos_bench_")
    scenarios = {
        "live_d1": dict(mode="live", depth=1),
        "live_d2": dict(mode="live", depth=2),
        "checkpoint_d1": dict(mode="checkpoint", depth=1,
                              ckpt_dir=os.path.join(work_dir,
                                                    "chaos_ckpt")),
    }
    results = {}
    for name, sc in scenarios.items():
        results[name] = _run_scenario(steps=steps, kill_iter=kill_iter,
                                      dim=dim, n_leaves=n_leaves,
                                      sleep_s=sleep_s, **sc)
    live_s = results["live_d1"]["recovery_s"]
    ckpt_s = results["checkpoint_d1"]["recovery_s"]
    summary = {
        "workload": {"steps": steps, "kill_iter": kill_iter, "dim": dim,
                     "n_leaves": n_leaves, "sleep_s": sleep_s,
                     "devices": len(jax.devices()),
                     "param_bytes_per_model": n_leaves * dim * dim * 4},
        **results,
        "live_vs_checkpoint_speedup": ckpt_s / max(live_s, 1e-9),
        "all_bit_identical": all(r["bit_identical"]
                                 for r in results.values()),
    }
    rows = []
    for name in ("live_d1", "live_d2", "checkpoint_d1"):
        r = results[name]
        rows.append((f"chaos/{name}", r["recovery_s"] * 1e6,
                     f"restore_s={r['restore_s']:.4f};"
                     f"reshard_s={r['reshard_s']:.4f};"
                     f"moved={r['moved_bytes']};"
                     f"identical={r['bit_identical']}"))
    rows.append(("chaos/live_vs_checkpoint", 0.0,
                 f"speedup={summary['live_vs_checkpoint_speedup']:.2f}x"))
    rows.append(("chaos/bit_identical", 0.0,
                 f"all={summary['all_bit_identical']}"))
    return rows, summary


def bench_preempt(steps=6, notice_iter=2, dim=512, n_leaves=8, sleep_s=0.01,
                  deadline_s=60.0, **_ignored):
    """Preemption-notice migration vs the reactive live-recovery path on
    the same loss; returns (csv_rows, json_summary)."""
    import jax
    # warm-up (see bench_chaos): the measured recoveries must be warm
    _run_scenario(mode="live", depth=1, steps=3, kill_iter=1, dim=dim,
                  n_leaves=n_leaves, sleep_s=0.0)
    reactive = _run_scenario(mode="live", depth=1, steps=steps,
                             kill_iter=notice_iter, dim=dim,
                             n_leaves=n_leaves, sleep_s=sleep_s)
    migrate = _run_preempt(steps=steps, notice_iter=notice_iter,
                           deadline_s=deadline_s, dim=dim,
                           n_leaves=n_leaves, sleep_s=sleep_s)
    summary = {
        "workload": {"steps": steps, "notice_iter": notice_iter, "dim": dim,
                     "n_leaves": n_leaves, "sleep_s": sleep_s,
                     "deadline_s": deadline_s,
                     "devices": len(jax.devices()),
                     "param_bytes_per_model": n_leaves * dim * dim * 4},
        "migrate": migrate,
        "reactive_live": reactive,
        "migrate_vs_reactive_speedup": (reactive["recovery_s"]
                                        / max(migrate["recovery_s"], 1e-9)),
        "migrate_faster": migrate["recovery_s"] < reactive["recovery_s"],
        "all_bit_identical": (migrate["bit_identical"]
                              and reactive["bit_identical"]),
    }
    rows = [
        ("chaos/preempt_migrate", migrate["recovery_s"] * 1e6,
         f"drain_s={migrate['drain_s']:.4f};"
         f"replan_s={migrate['replan_s']:.4f};"
         f"aborted={migrate['aborted_calls']};"
         f"restores={migrate['checkpoint_restores']};"
         f"identical={migrate['bit_identical']}"),
        ("chaos/preempt_reactive_live", reactive["recovery_s"] * 1e6,
         f"reshard_s={reactive['reshard_s']:.4f};"
         f"identical={reactive['bit_identical']}"),
        ("chaos/preempt_vs_reactive", 0.0,
         f"speedup={summary['migrate_vs_reactive_speedup']:.2f}x;"
         f"migrate_faster={summary['migrate_faster']}"),
    ]
    return rows, summary


def bench_straggler(steps=5, delay_iter=1, delay_s=0.5, dim=256, n_leaves=8,
                    sleep_s=0.01, **_ignored):
    """Speculative straggler re-dispatch vs eating the stall; returns
    (csv_rows, json_summary)."""
    import jax
    kw = dict(steps=steps, delay_iter=delay_iter, delay_s=delay_s, dim=dim,
              n_leaves=n_leaves, sleep_s=sleep_s)
    # warm-up: JAX compile/dispatch of the clone-reshard path
    _run_straggler(speculate=True, **{**kw, "steps": 3, "sleep_s": 0.0,
                                     "delay_s": 0.2})
    baseline = _run_straggler(speculate=False, **kw)
    spec = _run_straggler(speculate=True, **kw)
    assert spec["speculative_dispatches"] >= 1, spec
    summary = {
        "workload": {"steps": steps, "delay_iter": delay_iter,
                     "delay_s": delay_s, "dim": dim, "n_leaves": n_leaves,
                     "sleep_s": sleep_s, "devices": len(jax.devices())},
        "speculative": spec,
        "no_speculation": baseline,
        "wall_speedup": baseline["wall_s"] / max(spec["wall_s"], 1e-9),
        "speculation_faster": spec["wall_s"] < baseline["wall_s"],
        "all_bit_identical": (spec["bit_identical"]
                              and baseline["bit_identical"]),
    }
    rows = [
        ("chaos/straggler_speculative", spec["wall_s"] * 1e6,
         f"dispatches={spec['speculative_dispatches']};"
         f"wins={spec['speculative_wins']};"
         f"identical={spec['bit_identical']}"),
        ("chaos/straggler_baseline", baseline["wall_s"] * 1e6,
         f"stragglers={baseline['stragglers']};"
         f"identical={baseline['bit_identical']}"),
        ("chaos/straggler_vs_baseline", 0.0,
         f"speedup={summary['wall_speedup']:.2f}x;"
         f"faster={summary['speculation_faster']}"),
    ]
    return rows, summary


BENCHES = {"kill": bench_chaos, "preempt": bench_preempt,
           "straggler": bench_straggler}


def _bench_scenarios(scenario: str, **kw):
    """Run one scenario (or all), merging rows and summaries."""
    names = list(BENCHES) if scenario == "all" else [scenario]
    rows, summary = [], {}
    for name in names:
        r, s = BENCHES[name](**kw)
        rows.extend(r)
        summary[name] = s
    if len(names) == 1:
        return rows, summary[names[0]]
    return rows, summary


def run(smoke: bool = False, json_path: str | None = None,
        scenario: str = "all"):
    """Entry point for ``benchmarks.run --only chaos``: the core runs in a
    CPU child with forced host devices so the recovery reshards are real
    multi-device collectives; a failed child fails the bench."""
    from benchmarks.common import spawn_host_devices
    args_list = (["--core", "--scenario", scenario]
                 + (["--smoke"] if smoke else []))
    if json_path:
        args_list += ["--json", json_path]
    return spawn_host_devices("benchmarks.chaos_bench", args_list, "chaos/",
                              N_NODES * DEVS_PER_NODE)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--core", action="store_true",
                    help="run the measurement in this process (set by the "
                         "spawning parent after forcing host devices)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-friendly: fewer steps, smaller weights")
    ap.add_argument("--scenario", default="all",
                    choices=["kill", "preempt", "straggler", "all"],
                    help="which chaos scenario(s) to run")
    ap.add_argument("--json", default=None,
                    help="write the summary dict to this path")
    args = ap.parse_args()

    from benchmarks.common import emit, platform_rows
    kw = {"steps": 4, "dim": 256, "sleep_s": 0.005} if args.smoke else {}
    if args.core:
        rows, summary = _bench_scenarios(args.scenario, **kw)
        emit(platform_rows(rows))
        if args.json:
            with open(args.json, "w") as f:
                json.dump(summary, f, indent=2)
        return
    rows = run(smoke=args.smoke, json_path=args.json,
               scenario=args.scenario)
    emit(rows)


if __name__ == "__main__":
    main()
