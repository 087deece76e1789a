"""Pipelined multi-iteration runtime benchmark (real wall time, CPU-safe).

Runs a PPO-shaped toy graph — actor generate+train on one mesh half, frozen
reward inference + critic train on the other, with a real parameter reshard
between the actor's gen and train layouts — through the runtime twice:

  barriered   — the per-iteration ``run_iteration`` loop (event loop and
                prefetch chains torn down at every boundary)
  pipelined   — ``run(steps=k, pipeline_depth=2)`` on one persistent event
                loop: iteration t+1's generation (and its prefetched
                reallocation) overlaps iteration t's critic-train tail

and reports steady-state per-iteration wall time, bubble fraction (idle
device-time share), cross-iteration prefetch hits, and the byte-accurate
reshard split (moved bytes per reshard vs the whole-tree size — only half
the actor's leaves change layout between gen and train).  A depth-1 parity
check asserts the pipelined scheduler reproduces the sequential engine's
data pools bit-for-bit.

The core runs in a CPU subprocess with 4 forced host devices so the
reshard is a genuine multi-device collective; its rows say
``platform=cpu``, and a failed subprocess fails the bench.

Wired into ``benchmarks/run.py`` as ``--only pipeline``; CI runs
``--smoke --json`` and uploads the artifact.
"""

from __future__ import annotations

import argparse
import json
import time


def _toy_engine(sleep_s, ctrain_factor=3.0, dim=192, n_leaves=8):
    """Build (dfg, plan, make_models, sharding_for, executors).  Half the
    actor's leaves change layout between the gen and train assignments."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.dfg import (DataflowGraph, FunctionCall, GENERATE,
                                INFERENCE, TRAIN, Workload)
    from repro.core.plan import (Assignment, Cluster, DeviceMesh,
                                 ExecutionPlan, ParallelStrategy)
    from repro.core.runtime import ModelState

    n_dev = len(jax.devices())
    half = max(n_dev // 2, 1)
    cluster = Cluster(n_nodes=1, devs_per_node=n_dev)
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
    dfg = DataflowGraph(calls, "toy")
    mesh_a = DeviceMesh(0, 1, 0, half)
    mesh_b = (DeviceMesh(0, 1, half, n_dev - half) if n_dev > 1 else mesh_a)
    gen_asg = Assignment(mesh_a, ParallelStrategy(half, 1, 1, 1))
    trn_asg = Assignment(mesh_a, ParallelStrategy(1, half, 1, 1)) \
        if half > 1 else Assignment(mesh_a, ParallelStrategy(1, 1, 1, 2))
    b_asg = Assignment(mesh_b, ParallelStrategy(mesh_b.size, 1, 1, 1))
    plan = ExecutionPlan({"gen": gen_asg, "rew": b_asg,
                          "atrain": trn_asg, "ctrain": b_asg}, cluster)

    jmesh = jax.make_mesh((half,), ("x",))
    sh_gen = NamedSharding(jmesh, P("x", None) if half > 1 else P())
    sh_trn = NamedSharding(jmesh, P(None, "x") if half > 1 else P(None))
    sh_stay = NamedSharding(jmesh, P())

    def sharding_for(model_name, asg):
        if model_name != "actor":
            return None
        moving = sh_trn if asg is plan.assignments["atrain"] \
            or asg == plan.assignments["atrain"] else sh_gen
        # half the leaves flip layout between gen and train; the other half
        # (think frozen embeddings / norms) keeps a replicated layout — the
        # byte-accurate prefetch must skip them
        dst = {}
        for i in range(n_leaves):
            dst[f"w{i}"] = moving if i < n_leaves // 2 else sh_stay
        return dst

    def make_models():
        params = {}
        for i in range(n_leaves):
            sh = sh_gen if i < n_leaves // 2 else sh_stay
            params[f"w{i}"] = jax.device_put(
                jnp.ones((dim, dim), jnp.float32), sh)
        return {"actor": ModelState(params,
                                    assignment=plan.assignments["gen"]),
                "reward": ModelState({}),
                "critic": ModelState({})}

    def mk(name, outs, slp):
        def ex(ms, inputs):
            time.sleep(slp)
            return {k: (name, tuple(sorted(
                (kk, vv) for kk, vv in inputs.items()
                if isinstance(vv, (int, tuple, str))))) for k in outs}
        return ex

    executors = {
        "gen": mk("gen", ("seq",), sleep_s),
        "rew": mk("rew", ("r",), sleep_s),
        "atrain": mk("atrain", ("a_out",), sleep_s),
        "ctrain": mk("ctrain", ("c_out",), ctrain_factor * sleep_s),
    }
    return dfg, plan, make_models, sharding_for, executors


def _iter_bounds(records, base):
    """(first-iteration end, last-iteration end, start) from CallRecords."""
    by_iter = {}
    for r in records:
        by_iter.setdefault(r.iteration - base, []).append(r)
    ends = {t: max(r.end for r in rs) for t, rs in by_iter.items()}
    start = min(r.start for rs in by_iter.values() for r in rs)
    return ends, start


def _bubble_frac(records, plan, cluster):
    """Idle share of device-time over the run's makespan: 1 - busy/(P*T)."""
    m = cluster.devs_per_node
    wall0 = min(r.start for r in records)
    wall1 = max(r.end for r in records)
    devs = set()
    busy = 0.0
    from repro.core.dfg import base_name
    for r in records:
        d = plan.assignments[base_name(r.name)].mesh.devices(m)
        devs |= d
        busy += (r.end - r.start) * len(d)
    span = max(wall1 - wall0, 1e-9)
    return max(0.0, 1.0 - busy / (span * max(len(devs), 1)))


def bench_pipeline(steps=8, sleep_s=0.05, pipeline_depth=2):
    """Returns (csv_rows, json_summary)."""
    from repro.core.runtime import RuntimeEngine
    from repro.parallel.realloc_exec import realloc_bytes

    # ---- barriered baseline: one run_iteration per step
    dfg, plan, make_models, sharding_for, executors = _toy_engine(sleep_s)
    eng_b = RuntimeEngine(dfg, plan, executors, make_models(),
                          sharding_for=sharding_for)
    for t in range(steps):
        eng_b.run_iteration({"prompts": t})
    ends_b, start_b = _iter_bounds(eng_b.records, 0)
    # steady state: difference out the first (compile-warm-up) iteration
    steady_b = (ends_b[steps - 1] - ends_b[0]) / (steps - 1)
    stats_b = eng_b.stats()

    # ---- pipelined: one persistent run at depth
    dfg, plan, make_models, sharding_for, executors = _toy_engine(sleep_s)
    models = make_models()
    whole_tree = realloc_bytes(models["actor"].params)
    eng_p = RuntimeEngine(dfg, plan, executors, models,
                          sharding_for=sharding_for,
                          pipeline_depth=pipeline_depth)
    eng_p.run(lambda t: {"prompts": t}, steps=steps)
    ends_p, start_p = _iter_bounds(eng_p.records, 0)
    steady_p = (ends_p[steps - 1] - ends_p[0]) / (steps - 1)
    stats_p = eng_p.stats()
    moved = sorted({r.realloc_bytes for r in eng_p.records
                    if r.realloc_bytes > 0})

    # ---- depth-1 parity: pipelined scheduler == sequential engine pools
    dfg, plan, make_models, sharding_for, executors = _toy_engine(0.0)
    eng_1 = RuntimeEngine(dfg, plan, executors, make_models(),
                          sharding_for=sharding_for, pipeline_depth=1)
    pooled = eng_1.run(lambda t: {"prompts": t}, steps=3)
    dfg, plan, make_models, sharding_for, executors = _toy_engine(0.0)
    eng_s = RuntimeEngine(dfg, plan, executors, make_models(),
                          sharding_for=sharding_for)
    sequential = [eng_s.run_iteration({"prompts": t}) for t in range(3)]
    parity = pooled == sequential

    speedup = steady_b / max(steady_p, 1e-9)
    summary = {
        "workload": {"steps": steps, "sleep_s": sleep_s,
                     "pipeline_depth": pipeline_depth,
                     "devices": len(__import__("jax").devices())},
        "barriered": {"steady_iter_s": steady_b,
                      "wall_s": stats_b["wall_s"],
                      "bubble_frac": _bubble_frac(eng_b.records, plan,
                                                  plan.cluster),
                      "prefetch_hits": stats_b["prefetch_hits"],
                      "cross_iter_prefetch_hits":
                          stats_b["cross_iter_prefetch_hits"]},
        "pipelined": {"steady_iter_s": steady_p,
                      "wall_s": stats_p["wall_s"],
                      "bubble_frac": _bubble_frac(eng_p.records, plan,
                                                  plan.cluster),
                      "prefetch_hits": stats_p["prefetch_hits"],
                      "cross_iter_prefetch_hits":
                          stats_p["cross_iter_prefetch_hits"]},
        "speedup": speedup,
        "reshard": {"moved_bytes_per_reshard": moved,
                    "whole_tree_bytes": whole_tree,
                    "moved_frac": (moved[-1] / whole_tree) if moved else 0.0,
                    "realloc_bytes_total": stats_p["realloc_bytes"]},
        "parity_depth1": parity,
    }
    rows = [
        ("pipeline/barriered_iter", steady_b * 1e6,
         f"bubble={summary['barriered']['bubble_frac']:.2f}"),
        ("pipeline/pipelined_iter", steady_p * 1e6,
         f"bubble={summary['pipelined']['bubble_frac']:.2f};"
         f"depth={pipeline_depth}"),
        ("pipeline/speedup", 0.0, f"pipelined_over_barriered={speedup:.2f}x"),
        ("pipeline/prefetch", 0.0,
         f"hits={stats_p['prefetch_hits']};"
         f"cross_iter={stats_p['cross_iter_prefetch_hits']}"),
        ("pipeline/reshard_bytes", 0.0,
         f"moved={moved[-1] if moved else 0};whole_tree={whole_tree};"
         f"frac={summary['reshard']['moved_frac']:.2f}"),
        ("pipeline/parity_depth1", 0.0, f"bit_for_bit={parity}"),
    ]
    return rows, summary


def run(smoke: bool = False, json_path: str | None = None):
    """Entry point for ``benchmarks.run --only pipeline``: the core runs in
    a CPU child with 4 forced host devices so the reshard is a real
    multi-device collective; a failed child fails the bench."""
    from benchmarks.common import spawn_host_devices
    args_list = ["--core"] + (["--smoke"] if smoke else [])
    if json_path:
        args_list += ["--json", json_path]
    return spawn_host_devices("benchmarks.pipeline_bench", args_list,
                              "pipeline/")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--core", action="store_true",
                    help="run the measurement in this process (set by the "
                         "spawning parent after forcing host devices)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-friendly: fewer steps, shorter sleeps")
    ap.add_argument("--json", default=None,
                    help="write the summary dict to this path")
    args = ap.parse_args()

    from benchmarks.common import emit, platform_rows
    kw = {"steps": 5, "sleep_s": 0.03} if args.smoke else {}
    if args.core:
        rows, summary = bench_pipeline(**kw)
        emit(platform_rows(rows))
        if args.json:
            with open(args.json, "w") as f:
                json.dump(summary, f, indent=2)
        return
    rows = run(smoke=args.smoke, json_path=args.json)
    emit(rows)


if __name__ == "__main__":
    main()
