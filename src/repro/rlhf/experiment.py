"""User-facing experiment API (paper Appendix B, Fig. 18).

``RLHFExperiment`` takes the algorithm name + model configs + workload, runs
the plan search under the hood (the paper's ``@auto`` decorator), builds the
jitted executors for every model function call, and returns a RuntimeEngine
ready to run iterations with parameter reallocation.

This is the end-to-end integration of the paper's technique: search -> plan
-> runtime -> reallocation, with real JAX computation behind every call.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.data import packing
from repro.core import dfg as DFG
from repro.core import fault as FLT
from repro.core import tracing
from repro.core.estimator import CostModel, Profile
from repro.core.plan import Cluster, ExecutionPlan
from repro.core.runtime import ModelState, RuntimeEngine
from repro.core.search import heuristic_plan, mcmc_search
from repro.kernels import ops as OPS
from repro.models import model as MDL
from repro.optim import adamw
from repro.rlhf import ppo as PPO
from repro.rlhf import reward as RWD


@dataclasses.dataclass
class ExperimentConfig:
    algorithm: str = "ppo"
    batch: int = 8
    prompt_len: int = 16
    gen_len: int = 16
    seed: int = 0
    ppo: PPO.PPOHyperparameters = dataclasses.field(
        default_factory=PPO.PPOHyperparameters)
    opt: adamw.AdamWConfig = dataclasses.field(default_factory=adamw.AdamWConfig)
    search_iters: int = 300
    impl: str = "reference"
    # rollout-only kernel tier ("pallas" routes the decode loop through
    # kernels/ops.decode_mha -> Pallas flash_decode while training stays on
    # ``impl``); None inherits ``impl``.
    rollout_impl: Optional[str] = None
    fused_sampling: bool = True  # fused decode+sample rollout hot path
    eos_id: Optional[int] = None  # enables EOS-early-exit generation
    sampler: str = "cdf"  # "cdf" (fast) or "gumbel" (seed-identical draws)
    # truncated sampling, fused into ops.sample_logits (0 / 1.0 = off)
    top_k: int = 0
    top_p: float = 1.0
    # serve-path engine (launch/serve.build_server): "bucketed" keeps the
    # run-to-completion bucket loop; "continuous" uses the paged-KV
    # continuous-batching engine
    serve_mode: str = "continuous"
    kv_block_size: int = 16  # tokens per paged-KV block
    max_kv_blocks: int = 0  # total pool blocks (0 = worst-case auto-size)
    # checkpoint every N iterations through checkpoint/manager.py (0 = off)
    checkpoint_every: int = 0
    checkpoint_dir: Optional[str] = None
    # closed-loop calibration (docs/CALIBRATION.md): path of a
    # core/profiler.ProfileStore JSON.  When set and the store holds an
    # entry for the actor config on this hardware, the plan search runs on
    # the calibrated CostModel instead of the pure analytic one, and
    # save_profile() persists runtime-refitted scales back.
    profile_path: Optional[str] = None
    # fold live CallRecords back into the cost model and re-rank the plan
    # every N completed calls (0 = off); see RuntimeEngine.recalibrate
    recalibrate_every: int = 0
    # iterations of the concatenated dataflow graph in flight at once in
    # ``run(steps=k)`` (paper §4).  1 = barriered per-iteration execution.
    # Depths > 1 overlap frozen-model (ref/reward) inference and parameter
    # reallocations of iteration t+1 with iteration t's training tail; the
    # graph's parameter-version edges still gate every trainable model, so
    # PPO rollouts are never generated from stale weights (the on-policy
    # guard).  Algorithms *without* version edges on a sampled model would
    # lose that guarantee — keep depth 1 there.  With depth > 1 the plan
    # search and recalibration rank plans on steady-state per-iteration
    # time over the unrolled graph instead of the cold-start makespan.
    pipeline_depth: int = 1
    # elastic fault tolerance (core/fault.py, docs/ARCHITECTURE.md):
    # ``retry`` governs transient call failures (the default reproduces the
    # historical single retry); ``max_recoveries`` bounds host-loss
    # recoveries per run() — the engine masks the dead host, replans on the
    # survivors, reshards live weights (checkpoint restore when every
    # replica died) and resumes from the last retired iteration;
    # ``replan_iters`` sizes the recovery-path MCMC (short: it sits on the
    # recovery critical path, and it is seeded with the old plan's
    # projection so short chains are safe).
    retry: FLT.RetryPolicy = dataclasses.field(
        default_factory=FLT.RetryPolicy)
    max_recoveries: int = 2
    replan_iters: int = 60
    # speculative straggler re-dispatch (RuntimeEngine): race a duplicate
    # of a straggling call on an idle mesh, first finisher wins.  The
    # experiment restricts duplication to INFERENCE — actor_gen folds a
    # stateful RNG split, so a GENERATE re-run is not idempotent here.
    speculative_redispatch: bool = False
    # packed variable-length training (data/packing.py): train steps run on
    # the (total_tokens,) cu_seqlens layout — varlen attention, dropless
    # MoE over real tokens, packed PPO losses — instead of (B, S) padding.
    # Rollout/inference paths are unchanged; train cost scales with real
    # token counts (and the estimator keys on them, Workload.total_tokens).
    packed_training: bool = False
    # speculative draft-and-verify rollout (models/spec.py): a small frozen
    # draft model proposes spec_k tokens per cycle, the actor verifies them
    # in one prefill-shaped dispatch, rejection sampling keeps the rollout
    # distribution exactly the actor's (logprobs stay PPO-exact).  The
    # draft is a first-class planned model: build_ppo adds a draft_gen
    # call, the searcher places it on its own sub-mesh, and measured
    # accept rates feed back into the CostModel (record_accept_rate).
    # Must share the actor's vocab and be attention-only; EOS early-exit
    # (eos_id) is not supported on the speculative path.
    draft_model: Optional[ModelConfig] = None
    spec_k: int = 4  # draft length (fixed, or the initial value if adaptive)
    # re-pick k every cycle from the measured accept-rate EMA and the
    # calibrated estimator's cycle cost (models.spec.SpecController)
    spec_adaptive: bool = True


class RLHFExperiment:
    """PPO experiment: 4 models, 6 function calls, searched execution plan."""

    def __init__(self, actor_cfg: ModelConfig, critic_cfg: ModelConfig,
                 cluster: Cluster, exp: ExperimentConfig,
                 plan: Optional[ExecutionPlan] = None,
                 search: bool = True,
                 fault_injector: Optional[FLT.FaultInjector] = None):
        self.actor_cfg, self.critic_cfg, self.exp = actor_cfg, critic_cfg, exp
        self.cluster = cluster
        if exp.packed_training:
            # fail at construction with one actionable line, not at trace
            # time deep inside a recurrent mixer (NotImplementedError)
            from repro.analysis.verify import packed_mixer_error
            for cfg in (actor_cfg, critic_cfg):
                msg = packed_mixer_error(cfg)
                if msg:
                    raise ValueError(msg)
        if exp.draft_model is not None:
            from repro.models.spec import check_spec_pair
            check_spec_pair(actor_cfg, exp.draft_model)  # fail at construction
            if exp.eos_id is not None:
                raise ValueError("eos_id early exit is not supported on the "
                                 "speculative rollout path; unset draft_model "
                                 "or eos_id")
        self.graph = DFG.build_ppo(
            actor_cfg, critic_cfg, batch=exp.batch, prompt_len=exp.prompt_len,
            gen_len=exp.gen_len, n_minibatches=exp.ppo.n_minibatches,
            packed=exp.packed_training, draft=exp.draft_model)
        opt = exp.opt
        self.cost = CostModel(cluster, adam_bytes=(
            2 * jnp.dtype(opt.state_dtype).itemsize
            + jnp.dtype(opt.master_dtype).itemsize))
        self.profile_store = None
        if exp.profile_path:
            from repro.core.profiler import ProfileStore, ProfileTable
            self.profile_store = ProfileStore(exp.profile_path)
            entry = self.profile_store.get(actor_cfg.name)
            if entry is not None:
                self.cost = entry.cost_model(cluster)
            else:  # attach an empty table so live records accumulate into it
                self.cost.table = ProfileTable(actor_cfg.name, {})
        if plan is None:
            if search:
                plan = mcmc_search(self.graph, cluster, self.cost,
                                   iters=exp.search_iters,
                                   seed=exp.seed,
                                   pipeline_iters=max(exp.pipeline_depth, 1)
                                   ).best_plan
            else:
                plan = heuristic_plan(self.graph, cluster, self.cost)
        self.plan = plan
        # the trainable set, derived from the dataflow graph's TRAIN calls
        # (single source of truth for checkpoint/restore/recovery paths)
        self._trainable = tuple(sorted({c.model_name for c in self.graph.calls
                                        if c.call_type == DFG.TRAIN}))
        self._build_models()
        self._build_executors()
        candidates = []
        if exp.recalibrate_every > 0:
            try:  # the symmetric baseline is the natural fallback candidate
                candidates.append(heuristic_plan(self.graph, cluster,
                                                 self.cost))
            except ValueError:
                pass
        self.engine = RuntimeEngine(self.graph, self.plan, self.executors,
                                    self.models, cost_model=self.cost,
                                    pipeline_depth=exp.pipeline_depth,
                                    recalibrate_every=exp.recalibrate_every,
                                    plan_candidates=candidates,
                                    retry_policy=exp.retry,
                                    fault_injector=fault_injector,
                                    replanner=self._replan_on_topology,
                                    restore_models=self._restore_lost,
                                    max_recoveries=exp.max_recoveries,
                                    speculative_redispatch=(
                                        exp.speculative_redispatch),
                                    speculative_types=(DFG.INFERENCE,))
        self.iteration = 0
        self.ckpt = None
        if exp.checkpoint_every > 0:
            from repro.checkpoint.manager import CheckpointManager
            self.ckpt = CheckpointManager(exp.checkpoint_dir or "checkpoints")

    # ------------------------------------------------------------- models
    def _build_models(self):
        rngs = jax.random.split(jax.random.PRNGKey(self.exp.seed), 4)
        a, c = self.actor_cfg, self.critic_cfg
        self.models = {
            "actor": ModelState(MDL.init_params(rngs[0], a, head="lm"),
                                adamw.init(self.exp.opt, {})),
            "ref": ModelState(MDL.init_params(rngs[0], a, head="lm")),
            "critic": ModelState(MDL.init_params(rngs[2], c, head="value")),
            "reward": ModelState(MDL.init_params(rngs[3], c, head="value")),
        }
        self.models["actor"].opt_state = adamw.init(
            self.exp.opt, self.models["actor"].params)
        self.models["critic"].opt_state = adamw.init(
            self.exp.opt, self.models["critic"].params)
        if self.exp.draft_model is not None:
            # frozen proposal model (no TRAIN call, no opt state); its own
            # seed stream so shrinking the draft never perturbs the actor
            drng = jax.random.PRNGKey(self.exp.seed + 17)
            self.models["draft"] = ModelState(
                MDL.init_params(drng, self.exp.draft_model, head="lm"))

    # ---------------------------------------------------------- executors
    def _build_executors(self):
        exp, a_cfg, c_cfg = self.exp, self.actor_cfg, self.critic_cfg
        hp = exp.ppo
        gen_start = exp.prompt_len
        impl = exp.impl
        rollout_impl = exp.rollout_impl or impl
        for tier in (impl, rollout_impl):
            if tier not in OPS.IMPLS:
                raise ValueError(f"impl={tier!r} not in {OPS.IMPLS}")
        rng = jax.random.PRNGKey(exp.seed + 1)

        # named functions, not lambdas: the jitted program is named after
        # the function (``jit_actor_generate``), which is how a device
        # trace's module events say which call ran
        def actor_generate(p, b, k):
            return MDL.generate(
                p, a_cfg, b, num_new_tokens=exp.gen_len, rng=k,
                impl=rollout_impl, fused=exp.fused_sampling,
                eos_id=exp.eos_id, sampler=exp.sampler, top_k=exp.top_k,
                top_p=exp.top_p)

        # each call also returns its routing counters (empty without MoE),
        # which the executors add to the call's record (core/tracing.count)
        def ref_logprobs(p, toks):
            return PPO.sequence_logprobs(p, a_cfg, toks, gen_start,
                                         impl=impl, remat=False)

        def reward_scores(p, toks, m):
            return RWD.score_sequences(p, c_cfg, toks, m, impl=impl)

        def critic_values(p, toks):
            return PPO.sequence_values(p, c_cfg, toks, gen_start, impl=impl,
                                       remat=False)

        def counted(out):
            """The call's result, its routing counters added to the call's
            record."""
            tracing.count("moe", out[1])
            return out[0]

        # reward shaping + GAE of the padded train calls: one program,
        # compiled once per shape (hp is closed over, not traced)
        def advantages(final_reward, logp, ref_logp, values, mask):
            return PPO.advantages(hp, final_reward, logp, ref_logp, values,
                                  mask)

        gen_fn, ref_fn, rew_fn, val_fn, adv_fn = map(
            jax.jit, (actor_generate, ref_logprobs, reward_scores,
                      critic_values, advantages))
        if exp.packed_training:
            # one static max_seqlen (the padded S) keys the banded varlen
            # reference; per-iteration token totals vary but are bucketed
            # by pack_minibatches, so recompiles stay bounded
            a_step = PPO.make_packed_actor_train_step(
                a_cfg, hp, exp.opt, impl=impl,
                max_seqlen=exp.prompt_len + exp.gen_len)
            c_step = PPO.make_packed_critic_train_step(
                c_cfg, hp, exp.opt, impl=impl,
                max_seqlen=exp.prompt_len + exp.gen_len)
        else:
            a_step = PPO.make_actor_train_step(a_cfg, hp, exp.opt, gen_start,
                                               impl=impl)
            c_step = PPO.make_critic_train_step(c_cfg, hp, exp.opt,
                                                gen_start, impl=impl)

        def actor_train_step(params, opt_state, batch):
            return a_step(params, opt_state, batch)

        def critic_train_step(params, opt_state, batch):
            return c_step(params, opt_state, batch)

        actor_step = jax.jit(actor_train_step, donate_argnums=(0, 1))
        critic_step = jax.jit(critic_train_step, donate_argnums=(0, 1))

        def train(model, step, ms, batch):
            """One train step and the host sync of its stats."""
            with tracing.span("ppo.step", model=model):
                ms.params, ms.opt_state, stats = step(ms.params,
                                                      ms.opt_state, batch)
            tracing.count("moe", stats.pop("moe", {}))
            with tracing.span("ppo.sync", model=model):
                return jax.tree.map(float, stats)

        state = {"rng": rng}

        def actor_gen(ms, inputs):
            state["rng"], k = jax.random.split(state["rng"])
            out = gen_fn(ms.params, inputs["prompts"], k)
            tracing.count("moe", out.get("moe", {}))
            toks = jnp.concatenate([inputs["prompts"]["tokens"],
                                    out["tokens"]], axis=1)
            mask = out.get("gen_mask", jnp.ones_like(out["logprobs"]))
            return {"seq": toks, "logp": out["logprobs"], "gen_mask": mask}

        if exp.draft_model is not None:
            from repro.models import spec as SPEC
            controller = None
            if exp.spec_adaptive:
                # drive k from the same calibrated estimator that placed
                # both models, when the plan knows where they sit
                cycle_cost = None
                a_asg = self.plan.assignments.get("actor_gen")
                d_asg = self.plan.assignments.get("draft_gen")
                if a_asg is not None and d_asg is not None:
                    cycle_cost = self.cost.spec_cycle_time_fn(
                        a_cfg, exp.draft_model, exp.batch,
                        exp.prompt_len + exp.gen_len // 2, a_asg, d_asg)
                controller = SPEC.SpecController(
                    init_k=exp.spec_k, cycle_cost=cycle_cost)
            self.spec_controller = controller
            models = self.models

            def draft_gen(ms, inputs):
                # the plan places the draft here and the simulator costs
                # its dispatches/realloc edges; at runtime the proposal
                # stream is interleaved into the verify loop below, so
                # this call just publishes the dependency token
                b = inputs["prompts"]["tokens"].shape[0]
                return {"draft_seq": jnp.zeros((b,), jnp.int32)}

            def actor_gen_spec(ms, inputs):
                state["rng"], k = jax.random.split(state["rng"])
                out = SPEC.spec_generate(
                    ms.params, a_cfg, models["draft"].params,
                    exp.draft_model, inputs["prompts"],
                    num_new_tokens=exp.gen_len, spec_k=exp.spec_k, rng=k,
                    sampler=exp.sampler, top_k=exp.top_k, top_p=exp.top_p,
                    impl=rollout_impl, block_size=exp.kv_block_size,
                    controller=controller)
                # measured accept rate closes the estimator loop
                self.cost.record_accept_rate(
                    "actor", out["stats"]["accept_rate"])
                toks = jnp.concatenate([inputs["prompts"]["tokens"],
                                        out["tokens"]], axis=1)
                return {"seq": toks, "logp": out["logprobs"],
                        "gen_mask": jnp.ones_like(out["logprobs"]),
                        "spec_stats": out["stats"]}

            actor_gen = actor_gen_spec

        def reward_inf(ms, inputs):
            full_mask = jnp.ones(inputs["seq"].shape, jnp.float32)
            return {"rewards": counted(rew_fn(ms.params, inputs["seq"],
                                              full_mask))}

        def ref_inf(ms, inputs):
            return {"ref_logp": counted(ref_fn(ms.params, inputs["seq"]))}

        def critic_inf(ms, inputs):
            return {"values": counted(val_fn(ms.params, inputs["seq"]))}

        def estimate(model, inputs):
            with tracing.span("ppo.adv", model=model):
                return adv_fn(inputs["rewards"], inputs["logp"],
                              inputs["ref_logp"], inputs["values"],
                              inputs["gen_mask"])

        def actor_train(ms, inputs):
            adv, _ = estimate("actor", inputs)
            batch = {"tokens": inputs["seq"], "logp": inputs["logp"],
                     "adv": adv, "mask": inputs["gen_mask"]}
            return {"actor_stats": train("actor", actor_step, ms, batch)}

        def critic_train(ms, inputs):
            _, ret = estimate("critic", inputs)
            batch = {"tokens": inputs["seq"], "values": inputs["values"][:, :-1],
                     "ret": ret, "mask": inputs["gen_mask"]}
            return {"critic_stats": train("critic", critic_step, ms, batch)}

        # ---------------------------------------------- packed train path
        P, G = exp.prompt_len, exp.gen_len

        def _packed_prep(inputs):
            """Host-side repack of the padded rollout pool: per-sequence
            lens (keeping one post-EOS bootstrap token — GAE parity needs
            the carry entering the last valid token to be -V of its
            position) plus token-aligned (B, S) per-token arrays and the
            packed advantages/returns from the (T,) PPO math."""
            gm = np.asarray(jax.device_get(inputs["gen_mask"]))
            g_valid = gm.sum(-1).astype(np.int64)
            lens = P + np.minimum(g_valid + 1, G)
            b, s = inputs["seq"].shape
            z = jnp.zeros((b, s), jnp.float32)
            logp_full = z.at[:, P:].set(inputs["logp"])
            ref_full = z.at[:, P:].set(inputs["ref_logp"])
            mask_full = z.at[:, P:].set(inputs["gen_mask"])
            v_full = z.at[:, P - 1:].set(inputs["values"])
            cu = jnp.asarray(packing.cu_seqlens_of(lens))
            m_p = packing.pack(mask_full, lens)
            v_p = packing.pack(v_full, lens)
            shaped = PPO.shaped_rewards_packed(
                hp, inputs["rewards"], packing.pack(logp_full, lens),
                packing.pack(ref_full, lens), m_p, cu)
            adv, ret = PPO.gae_packed(hp, shaped, PPO.packed_shift_right(v_p),
                                      v_p, m_p, cu)
            return lens, s, logp_full, mask_full, adv, ret

        def actor_train_packed(ms, inputs):
            with tracing.span("ppo.adv", model="actor"):
                lens, s, logp_full, mask_full, adv, _ = _packed_prep(inputs)
            batch = packing.pack_minibatches(
                inputs["seq"],
                {"logp": logp_full, "adv": packing.unpack(adv, lens, s),
                 "mask": mask_full},
                lens, hp.n_minibatches)
            return {"actor_stats": train("actor", actor_step, ms, batch)}

        def critic_train_packed(ms, inputs):
            with tracing.span("ppo.adv", model="critic"):
                lens, s, _, mask_full, _, ret = _packed_prep(inputs)
            old_full = jnp.zeros_like(mask_full).at[:, P:].set(
                inputs["values"][:, :-1])
            batch = packing.pack_minibatches(
                inputs["seq"],
                {"values": old_full, "ret": packing.unpack(ret, lens, s),
                 "mask": mask_full},
                lens, hp.n_minibatches)
            return {"critic_stats": train("critic", critic_step, ms, batch)}

        if exp.packed_training:
            actor_train, critic_train = actor_train_packed, critic_train_packed

        self.executors = {
            "actor_gen": actor_gen, "reward_inf": reward_inf,
            "ref_inf": ref_inf, "critic_inf": critic_inf,
            "actor_train": actor_train, "critic_train": critic_train,
        }
        if exp.draft_model is not None:
            self.executors["draft_gen"] = draft_gen

    # ------------------------------------------------------------ running
    def make_prompts(self, rng):
        toks = jax.random.randint(
            rng, (self.exp.batch, self.exp.prompt_len), 0,
            self.actor_cfg.vocab_size, jnp.int32)
        return {"tokens": toks}

    def run_iteration(self, rng) -> dict:
        data = {"prompts": self.make_prompts(rng)}
        out = self.engine.run_iteration(data)
        self.iteration += 1
        if self.ckpt and self.iteration % self.exp.checkpoint_every == 0:
            self.save_checkpoint()
        return out

    def run(self, rng, steps: int) -> list[dict]:
        """Execute ``steps`` PPO iterations through the pipelined runtime
        (``ExperimentConfig.pipeline_depth`` iterations in flight; depth 1
        reproduces the sequential ``run_iteration`` loop bit-for-bit).
        Returns the per-iteration data pools in order.

        Checkpointing fires at iteration *retirement* — in order, once an
        iteration's calls all completed.  With ``pipeline_depth > 1`` the
        next iteration's train steps may already have run when iteration t
        retires, so a checkpoint snapshots weights at version >= t (the
        nominal iteration label is approximate).  When checkpointing is
        configured the engine quiesces running executors before each
        retirement hook, so the snapshot never races a donating train step
        and params/opt state are mutually consistent.
        """
        rngs = jax.random.split(rng, max(steps, 1))

        def data_for(t):
            return {"prompts": self.make_prompts(rngs[t])}

        def on_retire(t, pool):
            self.iteration += 1
            if self.ckpt and self.iteration % self.exp.checkpoint_every == 0:
                self.save_checkpoint()

        return self.engine.run(data_for, steps=steps, on_retire=on_retire,
                               quiesce_on_retire=self.ckpt is not None)

    # ------------------------------------------------------------ elasticity
    def _replan_on_topology(self, cluster: Cluster,
                            event) -> ExecutionPlan:
        """Engine callback on a topology change (host loss or gain): a
        short MCMC on the resized cluster, seeded with the old plan's
        projection so surviving assignments tend to stay put (their
        parameters then need no move at all)."""
        from repro.core.search import replan_on_topology
        notice = getattr(event, "kind", None) == "notice"
        plan = replan_on_topology(
            self.graph, cluster, self.cost, base_plan=self.plan,
            iters=self.exp.replan_iters, seed=self.exp.seed,
            pipeline_iters=max(self.exp.pipeline_depth, 1),
            avoid_nodes=tuple(event.nodes) if notice else ())
        if not notice:
            # a preemption notice plans on the SAME cluster (the doomed
            # host is excluded, not renumbered away — it is still up and
            # draining); only real loss/gain resizes the cluster
            self.cluster = cluster
        self.plan = plan
        return plan

    def _restore_lost(self, lost: list[str]):
        """Engine fallback when a model lost every replica: restore just
        those models (+ their opt states) from the newest valid
        checkpoint.  Models with a surviving replica are NOT touched —
        they recover live via resharding."""
        if self.ckpt is None:
            raise RuntimeError(
                f"models {lost} lost every replica and no checkpointing is "
                "configured (set ExperimentConfig.checkpoint_every)")
        template = {}
        for name in lost:
            template[name] = self.models[name].params
            if name in self._trainable:
                template[f"{name}_opt"] = self.models[name].opt_state
        self.ckpt.wait()
        _step, trees, _extra = self.ckpt.restore(template)
        for name in lost:
            self.models[name].params = trees[name]
            if f"{name}_opt" in trees:
                self.models[name].opt_state = trees[f"{name}_opt"]

    # ---------------------------------------------------------- calibration
    def save_profile(self) -> None:
        """Persist the (possibly runtime-refitted) calibrated cost model back
        into the profile store — the write half of the closed loop.  No-op
        unless ``profile_path`` was configured."""
        if self.profile_store is None:
            return
        self.profile_store.put_cost_model(self.actor_cfg.name, self.cost)
        self.profile_store.save()

    # -------------------------------------------------------- checkpointing
    def _checkpoint_trees(self) -> dict:
        trees = {name: ms.params for name, ms in self.models.items()}
        for name in self._trainable:
            trees[f"{name}_opt"] = self.models[name].opt_state
        return trees

    def save_checkpoint(self):
        """Snapshot all four models (+ trainable opt states) through the
        fault-tolerant manager; I/O overlaps the next iteration."""
        self.ckpt.save_async(self.iteration, self._checkpoint_trees(),
                             extra={"iteration": self.iteration})

    def restore_checkpoint(self, step: Optional[int] = None) -> int:
        """Load the latest (or a specific) checkpoint back into the live
        ``ModelState``s; returns the restored iteration number."""
        self.ckpt.wait()
        step, trees, extra = self.ckpt.restore(self._checkpoint_trees(), step)
        for name, ms in self.models.items():
            ms.params = trees[name]
        for name in self._trainable:
            self.models[name].opt_state = trees[f"{name}_opt"]
        self.iteration = int(extra.get("iteration", step))
        return self.iteration
