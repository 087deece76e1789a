"""The comparison that decides ``correct``, at a size a CPU test can hold.

A whole run of a tiny cell (the harness's look for a chip skipped) is
correct under the limits of ``qwen2-0.5b.ppo-b8-p128-g512``; the same run
with the timed path broken underneath is not, once for each fault a
one-chip training cell can have; and the control, the reference computed
with float8 matmuls in the program's place, is not either.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cell as C
from chipbench import check
from chipbench import run as R
from chipbench import weights as W

CELL = "qwen2-0.5b.ppo-b8-p128-g512"
SEED = 2 ** 40 + 12345


def tiny_cell() -> C.Cell:
    full = C.load_cell(CELL)
    config = {
        "name": "tiny", "arch": "qwen2-0.5b",
        "overrides": {"name": "tiny", "num_layers": 2, "n_superblocks": 2,
                      "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
                      "head_dim": 16, "d_ff": 128, "vocab_size": 512},
        "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 512, "rope_theta": 1e6, "rms_norm_eps": 1e-6,
        "torch_dtype": "bfloat16", "qkv_bias": True, "qk_norm": False,
        "family": "dense"}
    traffic = dict(full.traffic, prompt_len=8, gen_len=8, search_iters=5,
                   rollout_impl="reference")
    return C.Cell("tiny", 1, config, traffic, full.limits, full.end_to_end,
                  full.per_layer)


def run_tiny():
    with open(R.HERE / "peaks.json") as f:
        peak = json.load(f)["TPU v5 lite"]
    return R.run_cell(tiny_cell(), SEED, 0.2, False, jax.devices(), peak)


def test_sound_run_is_correct():
    res = run_tiny()
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["metrics"]["tokens_per_s"]["value"] > 0


def _frozen(step):
    def frozen(params, opt_state, batch):
        _, _, stats = step(params, opt_state, batch)
        return params, opt_state, stats
    return frozen


def _half_batch(step):
    def half(params, opt_state, batch):
        return step(params, opt_state,
                    jax.tree.map(lambda x: x[: x.shape[0] // 2], batch))
    return half


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "altered_token"])
def test_planted_fault_is_not_correct(fault, monkeypatch):
    from repro.models import model as MDL
    from repro.rlhf import ppo as PPO

    if fault == "altered_token":
        generate = MDL.generate

        def altered(params, cfg, batch, **kw):
            out = generate(params, cfg, batch, **kw)
            tok = out["tokens"]
            mid = tok.shape[1] // 2
            tok = tok.at[0, mid].set((tok[0, mid] + 1) % cfg.vocab_size)
            return dict(out, tokens=tok)

        monkeypatch.setattr(MDL, "generate", altered)
    else:
        wrap = _frozen if fault == "state_unchanged" else _half_batch
        for name in ("make_actor_train_step", "make_critic_train_step"):
            make = getattr(PPO, name)
            monkeypatch.setattr(PPO, name, lambda *a, make=make, **k:
                                wrap(make(*a, **k)))
    res = run_tiny()
    assert not res["correct"], res["checks"]
    over = [k for k, c in res["checks"].items()
            if c["limit"] is not None and c["value"] > c["limit"]]
    assert over, res["checks"]


def test_control_is_not_correct():
    cell = tiny_cell()
    key = jax.random.fold_in(W.seed_key(SEED), 1)
    rng = np.random.default_rng(7)
    seqs = [rng.integers(0, 512, (cell.batch, cell.prompt_len + cell.gen_len),
                         dtype=np.int32) for _ in range(3)]
    r32 = R.follow_reference(cell, key, seqs, 0)
    r8 = R.follow_reference(cell, key, seqs, 0, dot="fp8")
    ok, shown = check.verdict(check.numbers(check.as_program(r8), r32),
                              cell.limits)
    assert not ok, shown
    # the float32 reference against itself reads nothing
    same = check.numbers(check.as_program(r32), r32)
    assert max(same.values()) == 0.0


def test_no_limits_proves_nothing():
    ok, shown = check.verdict({"rollout_logp": 0.0}, None)
    assert not ok and shown["rollout_logp"]["limit"] is None


def test_weights_depend_on_every_seed_bit():
    a = jax.random.key_data(W.seed_key(2 ** 40 + 1))
    b = jax.random.key_data(W.seed_key(1))
    assert not jnp.array_equal(a, b)
