"""Rollout + reallocation hot-path microbenchmarks (real wall time, CPU-safe).

  rollout   — tokens/s of the fused-sampling decode loop vs the seed
              logits-carrying loop at the same config, plus the bucketed-jit
              compile count on a ragged prompt stream
  realloc   — critical-path reallocation seconds with the runtime's prefetch
              chains on vs off (same physical reshard), and prefetch hits

Wired into ``benchmarks/run.py`` as ``--only rollout``.
"""

from __future__ import annotations

import time


def _timeit(fn, *args, reps: int = 4):
    import jax
    jax.block_until_ready(fn(*args))  # compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def bench_rollout(batch=8, prompt_len=32, gen_len=64, vocab=32768, reps=4):
    """Returns (csv_rows, json_summary); the summary feeds back into the
    calibration profile via ``core.profiler.fold_rollout_summary``."""
    import jax
    from repro.configs import ARCHS
    from repro.models.model import generate, init_params, synth_batch

    cfg = ARCHS["qwen2-0.5b"].reduced(vocab_size=vocab)
    params = init_params(jax.random.PRNGKey(0), cfg)
    b = synth_batch(jax.random.PRNGKey(1), cfg, prompt_len, batch, "prefill")
    rows, tps = [], {}
    for name, kw in (("seed", dict(fused=False)),
                     ("fused", dict(fused=True, sampler="cdf"))):
        fn = jax.jit(lambda p, bb, k, kw=kw: generate(
            p, cfg, bb, num_new_tokens=gen_len, rng=k, **kw)["tokens"])
        dt = _timeit(fn, params, b, jax.random.PRNGKey(2), reps=reps)
        tps[name] = batch * gen_len / dt
        rows.append((f"rollout/{name}", dt / (batch * gen_len) * 1e6,
                     f"tok_s={tps[name]:.0f}"))
    rows.append(("rollout/speedup", 0.0,
                 f"fused_over_seed={tps['fused'] / tps['seed']:.2f}x"))
    summary = {"model": cfg.name, "batch": batch, "prompt_len": prompt_len,
               "gen_len": gen_len, "tok_s": tps}
    return rows, summary


def bench_bucketed(gen_len=8):
    import jax
    from repro.configs import ARCHS
    from repro.models.model import BucketedGenerator, init_params, synth_batch

    cfg = ARCHS["qwen2-0.5b"].reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    gen = BucketedGenerator(cfg)
    lengths = [9, 12, 15, 16, 21, 27, 31]  # 2 buckets, 7 distinct shapes
    t0 = time.perf_counter()
    for i, plen in enumerate(lengths):
        b = synth_batch(jax.random.PRNGKey(i), cfg, plen, 2, "prefill")
        gen(params, b, num_new_tokens=gen_len, rng=jax.random.PRNGKey(i))
    dt = time.perf_counter() - t0
    st = gen.stats()
    return [("rollout/bucketed", dt / len(lengths) * 1e6,
             f"shapes={len(lengths)};compiles={st['compiles']};"
             f"hits={st['hits']}")]


def _realloc_rows(dim=1024, compute_s=0.4):
    """One runtime iteration with a real reshard between two calls on the
    same model, with an independent call in between for the prefetch to hide
    under.  Reports critical-path realloc seconds with/without prefetch.
    Device-agnostic: on one device the reshard degenerates to a donated
    copy, but the prefetch-hit accounting is exercised identically."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.dfg import (DataflowGraph, FunctionCall, GENERATE,
                                INFERENCE, Workload)
    from repro.core.plan import (Assignment, Cluster, DeviceMesh,
                                 ExecutionPlan, ParallelStrategy)
    from repro.core.runtime import ModelState, RuntimeEngine

    n_dev = len(jax.devices())
    cluster = Cluster(n_nodes=1, devs_per_node=n_dev)
    w = Workload(batch=4, prompt_len=8, gen_len=8)
    calls = [
        FunctionCall("gen", "actor", GENERATE, None, w,
                     inputs=("prompts",), outputs=("seq",)),
        FunctionCall("other", "aux", INFERENCE, None, w,
                     inputs=("seq",), outputs=("x",)),
        FunctionCall("train", "actor", INFERENCE, None, w,
                     inputs=("x",), outputs=("y",)),
    ]
    dfg = DataflowGraph(calls, "toy")
    mesh_all = DeviceMesh(0, 1, 0, n_dev)
    gen_strategy = ParallelStrategy(n_dev, 1, 1, 1)
    # distinct even on 1 device (mbs marker) so the realloc edge exists
    train_strategy = (ParallelStrategy(n_dev // 2, 2, 1, 1) if n_dev > 1
                      else ParallelStrategy(1, 1, 1, 2))
    plan = ExecutionPlan({
        "gen": Assignment(mesh_all, gen_strategy),
        "other": Assignment(mesh_all, gen_strategy),
        "train": Assignment(mesh_all, train_strategy),
    }, cluster)

    jmesh = jax.make_mesh((n_dev,), ("data",))
    src_sh = NamedSharding(jmesh, P("data") if n_dev > 1 else P())
    dst_sh = NamedSharding(jmesh, P(None, "data") if n_dev > 1 else P(None))

    def sharding_for(model_name, asg):
        if model_name != "actor":
            return None
        shard = dst_sh if asg.strategy == train_strategy else src_sh
        return {f"w{i}": shard for i in range(8)}

    def fresh_models():
        params = {f"w{i}": jax.device_put(
            jnp.ones((dim, dim), jnp.float32), src_sh) for i in range(8)}
        return {"actor": ModelState(params,
                                    assignment=plan.assignments["gen"]),
                "aux": ModelState({"z": jnp.zeros(())})}

    executors = {
        "gen": lambda ms, inp: {"seq": 1},
        "other": lambda ms, inp: (time.sleep(compute_s), {"x": 2})[1],
        "train": lambda ms, inp: {
            "y": float(jax.block_until_ready(
                sum(jnp.sum(v) for v in ms.params.values())))},
    }

    rows = []
    stats = {}
    for prefetch in (False, True):
        eng = RuntimeEngine(dfg, plan, executors, fresh_models(),
                            sharding_for=sharding_for,
                            prefetch_realloc=prefetch)
        eng.run_iteration({"prompts": 0})
        st = eng.stats()
        stats[prefetch] = st
        tag = "prefetch" if prefetch else "serial"
        rows.append((f"realloc/{tag}", st["realloc_s"] * 1e6,
                     f"hits={st['prefetch_hits']}"))
    hidden = stats[False]["realloc_s"] - stats[True]["realloc_s"]
    rows.append(("realloc/overlapped", hidden * 1e6,
                 f"hidden_frac={hidden / max(stats[False]['realloc_s'], 1e-9):.2f}"))
    return rows


def bench_realloc_overlap(n_devices: int = 4):
    """Run the realloc-overlap iteration in a CPU child with forced host
    devices so the reshard is a genuine multi-device collective; a failed
    child fails the bench."""
    from benchmarks.common import spawn_host_devices
    return spawn_host_devices("benchmarks.rollout_bench", ["--realloc-only"],
                              "realloc/", n_devices)


def run():
    from benchmarks.common import platform_rows
    return (platform_rows(bench_rollout()[0] + bench_bucketed())
            + bench_realloc_overlap())


if __name__ == "__main__":
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("--realloc-only", action="store_true")
    ap.add_argument("--json", default=None,
                    help="write the rollout summary dict to this path "
                         "(foldable into a calibration profile via "
                         "core.profiler.fold_rollout_summary)")
    args = ap.parse_args()

    from benchmarks.common import emit, platform_rows
    if args.realloc_only:
        emit(platform_rows(_realloc_rows()))
    else:
        rows, summary = bench_rollout()
        emit(platform_rows(rows + bench_bucketed()) + bench_realloc_overlap())
        if args.json:
            with open(args.json, "w") as f:
                json.dump(summary, f, indent=2)
