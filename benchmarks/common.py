"""Shared benchmark scaffolding: clusters, baseline-system plan models, and
throughput accounting.

Baselines are modeled after the systems in the paper (§8.1, Appendix D):
  * dschat     — symmetric ZeRO-DP across all GPUs for every call
  * openrlhf   — asymmetric: actor/ref group, critic/reward group, dedicated
                 generation group; parameter sync actor_train -> gen
  * nemo       — two groups; actor train+gen colocated, critic/reward apart
  * heuristic  — REAL-Heuristic: symmetric Megatron-style 3D parallelism
  * real       — the searched plan (MCMC)
"""

from __future__ import annotations

import sys

from repro import hw
from repro.configs.llama import PAPER_SIZES, critic_of, LLAMA_7B
from repro.core.dfg import build_ppo
from repro.core.estimator import CostModel
from repro.core.plan import (Assignment, Cluster, DeviceMesh, ExecutionPlan,
                             ParallelStrategy)
from repro.core.search import heuristic_plan, mcmc_search
from repro.core.simulator import max_mem_per_device, simulate


def h100_cluster(n_gpus: int) -> Cluster:
    return Cluster(n_nodes=max(1, n_gpus // 8),
                   devs_per_node=min(8, n_gpus), chip=hw.H100,
                   intra_node_bw=450e9, inter_node_bw=50e9)


def ppo_workload(actor_size: str, n_gpus: int, batch=None, ctx=2048,
                 critic_size: str = "7b"):
    actor = PAPER_SIZES[actor_size]
    critic = critic_of(PAPER_SIZES[critic_size])
    batch = batch or 32 * n_gpus  # paper's weak scaling: 512 @ 16 GPUs
    return build_ppo(actor, critic, batch=batch, prompt_len=ctx // 2,
                     gen_len=ctx // 2, n_minibatches=8)


class Zero3CostModel(CostModel):
    """DeepSpeed ZeRO-3 semantics (DSChat / OpenRLHF training backend):
    params, grads and optimizer states shard over the DP group; every
    forward/backward pass all-gathers the full parameters layer-by-layer —
    cheap on memory, expensive on the wire (the inefficiency REAL exploits)."""

    def static_mem_per_dev(self, cfg, asg, opt_shard_dp=True):
        n = cfg.param_count()
        return n * 14.0 / asg.strategy.size

    def active_mem_per_dev(self, call, asg):
        base = super().active_mem_per_dev(call, asg)
        cfg, s = call.config, asg.strategy
        full = cfg.param_count() * 2.0 / (s.tp * s.pp)
        shard = cfg.param_count() * 2.0 / s.size
        biggest_layer = max(cfg.layer_params(sp) for sp in cfg.layers) * 2.0
        return base - full + shard + 2 * biggest_layer

    def _gather_time(self, cfg, asg, passes: float) -> float:
        s = asg.strategy
        if s.dp <= 1:
            return 0.0
        import repro.hw as hw
        wire = hw.all_gather_bytes(cfg.param_count() * 2.0, s.dp)
        return passes * wire / self._dp_bw(asg.mesh) * self.prof.comm_scale

    def call_cost(self, call, asg):
        import dataclasses as _dc
        base = super().call_cost(call, asg)
        s, w = asg.strategy, call.workload
        if call.call_type == "train":
            passes = 2.0 * s.mbs * w.n_minibatches  # fwd + bwd re-gather
        elif call.call_type == "inference":
            passes = 1.0 * s.mbs
        else:
            passes = 1.0  # generation reshards to TP first (HybridEngine)
        gather = self._gather_time(call.config, asg, passes)
        # DeepSpeed prefetches the next layer's gather under compute: only
        # the wire time exceeding compute is exposed
        exposed = max(0.0, gather - base.compute)
        return _dc.replace(base, comm=base.comm + exposed)


def dschat_plan(dfg, cluster) -> ExecutionPlan:
    """Symmetric ZeRO-3 DP everywhere; HybridEngine reshards generation to
    intra-node TP (the strategy switch creates the paper's realloc edge)."""
    mesh = cluster.full_mesh()
    s = ParallelStrategy(cluster.size, 1, 1, 8)
    tp = min(cluster.devs_per_node, cluster.size)
    gen = ParallelStrategy(cluster.size // tp, tp, 1, 1)
    asg = {}
    for c in dfg.calls:
        asg[c.name] = Assignment(mesh, gen if c.call_type == "generate" else s)
    return ExecutionPlan(asg, cluster)


def _column_split(cluster, fracs):
    """Split every node's device columns into groups (process-group model for
    baselines; not constrained to REAL's legal-mesh set)."""
    m = cluster.devs_per_node
    cols = [max(1, int(m * f)) for f in fracs]
    cols[-1] = m - sum(cols[:-1])
    out, start = [], 0
    for cwidth in cols:
        out.append(DeviceMesh(0, cluster.n_nodes, start, cwidth))
        start += cwidth
    return out


def openrlhf_plan(dfg, cluster) -> ExecutionPlan:
    """Three disjoint groups: vLLM generation / actor+ref / critic+reward."""
    if cluster.n_nodes >= 3:
        third = cluster.n_nodes // 3
        ga = DeviceMesh(0, third, 0, cluster.devs_per_node)
        gb = DeviceMesh(third, third, 0, cluster.devs_per_node)
        gc = DeviceMesh(2 * third, cluster.n_nodes - 2 * third, 0,
                        cluster.devs_per_node)
    else:
        ga, gb, gc = _column_split(cluster, (0.25, 0.5, 0.25))

    def mk(m, tp=1):
        tp = min(tp, m.dev_count, m.size)
        return Assignment(m, ParallelStrategy(m.size // tp, tp, 1, 32))

    asg = {
        "actor_gen": mk(ga, tp=min(4, ga.dev_count)),
        "ref_inf": mk(gb),
        "actor_train": mk(gb),
        "critic_inf": mk(gc),
        "reward_inf": mk(gc),
        "critic_train": mk(gc),
    }
    return ExecutionPlan({k: asg[k] for k in [c.name for c in dfg.calls]},
                         cluster)


def nemo_plan(dfg, cluster) -> ExecutionPlan:
    """Two groups: actor train+generation colocated; critic/reward/ref apart."""
    if cluster.n_nodes >= 2:
        half = cluster.n_nodes // 2
        ga = DeviceMesh(0, half, 0, cluster.devs_per_node)
        gb = DeviceMesh(half, cluster.n_nodes - half, 0, cluster.devs_per_node)
    else:
        ga, gb = _column_split(cluster, (0.5, 0.5))

    def mk(m, tp, pp=1):
        tp = min(tp, m.dev_count)
        while m.size % (tp * pp) or m.size // (tp * pp) < 1:
            pp = max(1, pp // 2)
        return Assignment(m, ParallelStrategy(m.size // (tp * pp), tp, pp, 32))

    pp_a = 2 if ga.size >= 16 else 1
    asg = {
        "actor_gen": mk(ga, min(8, ga.dev_count), pp_a),
        "actor_train": mk(ga, min(8, ga.dev_count), pp_a),
        "ref_inf": mk(gb, 1),
        "critic_inf": mk(gb, 1),
        "reward_inf": mk(gb, 1),
        "critic_train": mk(gb, 1),
    }
    return ExecutionPlan({k: asg[k] for k in [c.name for c in dfg.calls]},
                         cluster)


def plan_time(dfg, plan, cost, mem_penalty=True):
    sim = simulate(dfg, plan, cost)
    mem = max_mem_per_device(dfg, plan, cost)
    feasible = mem < cost.cluster.chip.hbm_bytes
    return sim.total_time, feasible


def throughput(dfg, seconds: float) -> float:
    """Tokens (prompt+generated) processed per second — the paper's metric."""
    w = dfg.by_name["actor_gen"].workload
    return w.batch * w.seq_len / seconds


def emit(rows):
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    sys.stdout.flush()
    return rows


def platform_rows(rows):
    """Append ``platform=<jax backend>`` to each row's derived column: the
    platform this process measured the row on."""
    import jax
    tag = f"platform={jax.default_backend()}"
    return [(name, us, f"{derived};{tag}" if derived else tag)
            for name, us, derived in rows]


def spawn_host_devices(module: str, args, prefix: str, n_devices: int = 4):
    """Run ``python -m <module> <args>`` in a child pinned to the CPU with
    ``n_devices`` forced host devices, so a multi-device path is rehearsed
    with real collectives on one host.  The child never asks for an
    accelerator: a parent that has touched JAX already holds it.

    Returns the child's ``prefix*`` CSV rows (tagged ``platform=cpu`` by
    the child).  A child that fails or prints no rows raises."""
    import os
    import subprocess

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(here, "src"), here, env.get("PYTHONPATH", "")])
    cmd = [sys.executable, "-m", module, *args]
    r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       timeout=600, cwd=here)
    rows = []
    for line in r.stdout.splitlines():
        parts = line.strip().split(",")
        if len(parts) == 3 and parts[0].startswith(prefix):
            rows.append((parts[0], float(parts[1]), parts[2]))
    if r.returncode != 0 or not rows:
        raise RuntimeError(
            f"{' '.join(cmd[1:])} exited {r.returncode} with {len(rows)} "
            f"{prefix} rows; stderr tail:\n{r.stderr[-4000:]}")
    return rows
