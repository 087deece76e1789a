"""Cluster launcher: search an execution plan and run RLHF training.

Single-host entry point; on a real fleet each host runs the same command
under its own process index and ``jax.distributed.initialize()`` stitches
the global device mesh — the plan/search/runtime layers are device-count
agnostic.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
        --algo ppo --steps 5 [--nodes 2 --devs-per-node 8]
    PYTHONPATH=src python -m repro.launch.train --plan-only --arch llama-7b \
        --nodes 2 --devs-per-node 8 --h100

``build_experiment`` and ``train`` are the build-and-run the command line
drives; ``chip_smoke.py`` calls the same two functions.
"""

from __future__ import annotations

import argparse
import time


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--algo", default="ppo", choices=["ppo"])
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen-len", type=int, default=8)
    ap.add_argument("--nodes", type=int, default=1)
    ap.add_argument("--devs-per-node", type=int, default=1)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-sized)")
    ap.add_argument("--rollout-impl", default=None,
                    choices=["reference", "pallas", "pallas_interpret"],
                    help="kernel tier of the rollout (default: training's)")
    ap.add_argument("--opt-state-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="AdamW m/v dtype (bfloat16 halves them)")
    ap.add_argument("--h100", action="store_true",
                    help="cost-model the paper's H100 cluster")
    ap.add_argument("--plan-only", action="store_true",
                    help="search + print the plan, do not execute")
    ap.add_argument("--search-iters", type=int, default=500)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--distributed", action="store_true",
                    help="multi-host: call jax.distributed.initialize()")
    return ap


def build_experiment(args):
    """The PPO experiment the arguments describe: ``--arch`` as actor and
    critic (ref/reward are copies), on a ``--nodes`` x ``--devs-per-node``
    cluster."""
    from repro import hw
    from repro.configs import ARCHS
    from repro.core.plan import Cluster
    from repro.optim.adamw import AdamWConfig
    from repro.rlhf.experiment import ExperimentConfig, RLHFExperiment
    from repro.rlhf.ppo import PPOHyperparameters

    cfg = ARCHS[args.arch]
    if args.smoke:
        cfg = cfg.reduced()
    kw = {}
    if args.h100:
        kw = dict(chip=hw.H100, intra_node_bw=450e9, inter_node_bw=50e9)
    cluster = Cluster(n_nodes=args.nodes, devs_per_node=args.devs_per_node,
                      **kw)
    exp_cfg = ExperimentConfig(
        batch=args.batch, prompt_len=args.prompt_len, gen_len=args.gen_len,
        search_iters=args.search_iters, rollout_impl=args.rollout_impl,
        opt=AdamWConfig(state_dtype=args.opt_state_dtype),
        ppo=PPOHyperparameters(n_minibatches=min(2, args.batch)))
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"cluster={args.nodes}x{args.devs_per_node}", flush=True)
    return RLHFExperiment(cfg, cfg, cluster, exp_cfg)


def train(run, steps: int, *, ckpt=None) -> list[dict]:
    """Run ``steps`` PPO iterations; returns one record per iteration
    (wall seconds, actor/critic loss, reward mean, ``kl``: the masked mean
    of rollout minus reference logprobs, ``logp_gap``: the masked mean of
    their absolute difference, and ``logp_mean``: the masked mean rollout
    logprob).  Every record's values are host floats, so each iteration's
    time covers its device work."""
    import jax
    import jax.numpy as jnp

    mgr = None
    if ckpt:
        from repro.checkpoint.manager import CheckpointManager
        mgr = CheckpointManager(ckpt)
    records = []
    for step in range(steps):
        t0 = time.perf_counter()
        out = run.run_iteration(jax.random.PRNGKey(step))
        mask = out["gen_mask"]
        masked_mean = lambda x: float(  # noqa: E731
            jnp.sum(x * mask) / jnp.maximum(jnp.sum(mask), 1.0))
        diff = out["logp"] - out["ref_logp"]
        rec = {"step": step,
               "actor_loss": float(out["actor_stats"]["loss"]),
               "critic_loss": float(out["critic_stats"]["loss"]),
               "reward_mean": float(out["rewards"].mean()),
               "kl": masked_mean(diff),
               "logp_gap": masked_mean(jnp.abs(diff)),
               "logp_mean": masked_mean(out["logp"])}
        rec["seconds"] = time.perf_counter() - t0
        records.append(rec)
        print(f"step {step}: {rec['seconds']:.1f}s "
              f"actor_loss={rec['actor_loss']:+.4f} "
              f"reward={rec['reward_mean']:+.3f}", flush=True)
        if mgr and (step + 1) % 5 == 0:
            mgr.save_async(step + 1, {
                "actor": run.models["actor"].params,
                "critic": run.models["critic"].params})
    if mgr:
        mgr.wait()
    return records


def main(argv=None):
    args = parser().parse_args(argv)
    import jax
    if args.distributed:
        jax.distributed.initialize()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    run = build_experiment(args)
    print(run.plan)
    if args.plan_only:
        return
    train(run, args.steps, ckpt=args.ckpt)
    print("done")


if __name__ == "__main__":
    main()
