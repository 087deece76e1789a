"""One benchmark cell: its files, the experiment it builds, and the record
of the iterations the reference follows.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
file ``configs/<config>.json`` and a traffic file ``traffic/<mix>.json``,
found by name.  ``limits/<cell>.json`` holds its correctness limits.  The
configuration's ``family`` names its architecture's module in ``archs/``.
"""

from __future__ import annotations

import dataclasses
import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import archs
from chipbench.reference import PPO

HERE = Path(__file__).resolve().parent
REPO = HERE.parent


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict | None
    end_to_end: list
    per_layer: list

    @property
    def family(self):
        """The architecture's module (``chipbench/archs``)."""
        return family_of(self.config)

    @property
    def arch(self):
        return self.family.Arch.from_file(self.config)

    @property
    def batch(self) -> int:
        return self.traffic["batch"]

    @property
    def prompt_len(self) -> int:
        return self.traffic["prompt_len"]

    @property
    def gen_len(self) -> int:
        return self.traffic["gen_len"]

    @property
    def tokens_per_iteration(self) -> int:
        return self.batch * (self.prompt_len + self.gen_len)

    @property
    def costs(self) -> dict:
        """FLOPs and bytes of each call of one PPO iteration."""
        return self.family.calls(self.arch, self.batch, self.prompt_len,
                                 self.gen_len,
                                 self.traffic["ppo"]["n_minibatches"])

    @property
    def hp(self) -> PPO:
        t = self.traffic
        return PPO(n_minibatches=t["ppo"]["n_minibatches"],
                   **{k: t["ppo"][k] for k in ("gamma", "lam", "clip_eps",
                                               "value_clip", "kl_coef")},
                   **{k: t["adamw"][k] for k in ("lr", "b1", "b2", "eps",
                                                 "weight_decay", "grad_clip",
                                                 "state_dtype")})


def family_of(config: dict):
    """The module of the architecture family the configuration names."""
    return archs.load(config.get("family"))


def load_cell(name: str, bench_path: Path = REPO / "BENCHMARK.json") -> Cell:
    bench = _load(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    limits_path = HERE / "limits" / f"{name}.json"
    cell = Cell(name=name, chips=w["chips"],
                config=_load(HERE / "configs" / f"{w['config']}.json"),
                traffic=_load(HERE / "traffic" / f"{w['traffic']}.json"),
                limits=_load(limits_path) if limits_path.exists() else None,
                end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])
    family_of(cell.config)  # a missing or unknown family fails here
    return cell


# ------------------------------------------------------------- the program

def model_config(config: dict):
    """The program's ``ModelConfig``: the registered architecture with the
    file's overrides, checked against what the file states (the family's
    ``stated``), and its parameter layout against the family's."""
    from repro.configs import ARCHS
    from repro.models import model as MDL
    cfg = dataclasses.replace(ARCHS[config["arch"]], **config["overrides"])
    family = family_of(config)
    a = family.Arch.from_file(config)
    wrong = {k: (getattr(cfg, k), v) for k, v in family.stated(a).items()
             if getattr(cfg, k) != v}
    if wrong:
        raise SystemExit(f"{config['name']}: program config differs from "
                         f"the file (program, file): {wrong}")
    for head in ("lm", "value"):
        want = jax.eval_shape(lambda: MDL.init_params(  # noqa: B023
            jax.random.PRNGKey(0), cfg, head=head))
        if want != family.layout(a, head):
            raise SystemExit(f"{config['name']}: the program's {head} "
                             "parameter layout differs from the family's "
                             "layout")
    return cfg


def prompts(key, batch: int, length: int, vocab: int):
    """Prompt token ids drawn uniformly over the (possibly sliced) vocab."""
    return jax.random.randint(key, (batch, length), 0, vocab, jnp.int32)


def build(cell: Cell, seed: int, weights: dict):
    """The experiment as ``launch/train.build_experiment`` builds it, for
    this cell's configuration and traffic, on the benchmark's weights."""
    from repro.core.plan import Cluster
    from repro.core.runtime import ModelState
    from repro.optim import adamw
    from repro.rlhf.experiment import ExperimentConfig, RLHFExperiment
    from repro.rlhf.ppo import PPOHyperparameters

    cfg = model_config(cell.config)
    arch, t = cell.arch, cell.traffic
    if t["ppo"].get("entropy_coef", 0.0) != 0.0:
        raise SystemExit("the reference has no entropy bonus")
    exp_cfg = ExperimentConfig(
        batch=cell.batch, prompt_len=cell.prompt_len, gen_len=cell.gen_len,
        seed=seed % (2 ** 31 - 1), search_iters=t["search_iters"],
        impl=t["impl"], rollout_impl=t["rollout_impl"],
        opt=adamw.AdamWConfig(**t["adamw"]),
        ppo=PPOHyperparameters(**t["ppo"]))

    class Experiment(RLHFExperiment):
        def _build_models(self):
            copy = functools.partial(jax.tree.map,
                                     lambda x: jnp.array(x, copy=True))
            self.models = {
                "actor": ModelState(weights["lm"]),
                "ref": ModelState(copy(weights["lm"])),
                "critic": ModelState(weights["value"]),
                "reward": ModelState(copy(weights["value"])),
            }
            init = jax.jit(functools.partial(adamw.init, self.exp.opt))
            for name in ("actor", "critic"):
                self.models[name].opt_state = init(self.models[name].params)

        def make_prompts(self, rng):
            return {"tokens": prompts(rng, cell.batch, cell.prompt_len,
                                      arch.vocab_size)}

    run = Experiment(cfg, cfg, Cluster(n_nodes=1, devs_per_node=cell.chips),
                     exp_cfg)
    weights.clear()  # the experiment holds the only references now
    return run


def record(out: dict) -> dict:
    """Host copy of what one iteration produced, for the comparison."""
    get = lambda k: np.asarray(jax.device_get(out[k]))  # noqa: E731
    return {"seq": get("seq"), "logp": get("logp"),
            "ref_logp": get("ref_logp"), "values": get("values"),
            "rewards": get("rewards"),
            "actor_loss": float(out["actor_stats"]["loss"]),
            "critic_loss": float(out["critic_stats"]["loss"])}


def opt_norms(run, what: str) -> dict:
    """Per-leaf norms of the actor's and critic's AdamW first moments
    (``what="m"``) or of their parameter change from the start, taken
    from the fp32 master copy against the frozen twin (``what="change"``:
    ref for the actor, reward for the critic)."""
    from chipbench.reference import diff_norms, leaf_norms
    out = {}
    for model, twin in (("actor", "ref"), ("critic", "reward")):
        opt = run.models[model].opt_state
        out[model] = (leaf_norms(opt["m"]) if what == "m" else
                      diff_norms(opt["master"], run.models[twin].params))
    return out
