"""Entry-point plumbing: the launcher's build-and-run that ``chip_smoke.py``
drives, and the persistent compile cache the entry points turn on."""

import math
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.launch import train as T

ROOT = Path(__file__).resolve().parents[1]


def test_launcher_plumbs_rollout_tier_and_opt_state_dtype():
    """``--rollout-impl``/``--opt-state-dtype`` reach the experiment, and
    ``train`` returns one finite record per PPO iteration."""
    args = T.parser().parse_args(
        ["--smoke", "--batch", "2", "--prompt-len", "8", "--gen-len", "4",
         "--search-iters", "20", "--rollout-impl", "pallas_interpret",
         "--opt-state-dtype", "bfloat16"])
    run = T.build_experiment(args)
    assert run.exp.rollout_impl == "pallas_interpret"
    assert run.exp.opt.state_dtype == "bfloat16"
    records = T.train(run, 2)
    assert [r["step"] for r in records] == [0, 1]
    for r in records:
        for key in ("seconds", "actor_loss", "critic_loss", "reward_mean",
                    "kl", "logp_gap", "logp_mean"):
            assert math.isfinite(r[key]), (key, r)
    # the rollout policy is the reference policy at iteration 0, and its
    # sampled tokens carry real (far from 0) logprobs
    assert abs(records[0]["kl"]) < 1e-3
    assert records[0]["logp_gap"] < 1e-3
    assert records[0]["logp_mean"] < -0.5 * math.log(run.actor_cfg.vocab_size)


def _cache_dir_in_child(env_dir):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    code = textwrap.dedent("""
        import jax
        from repro.launch.compile_cache import enable_compile_cache
        print(enable_compile_cache())
        print(jax.config.jax_compilation_cache_dir)
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True).stdout.split()
    return out[-2], out[-1]


@pytest.mark.parametrize("env_dir", [None, "/nonexistent/jax-cache"])
def test_compile_cache_directory(env_dir):
    """Unset: the fixed ``<checkout>/.cache/jax``.  Set: that directory
    only, as JAX itself reads it."""
    returned, configured = _cache_dir_in_child(env_dir)
    want = env_dir or str(ROOT / ".cache" / "jax")
    assert returned == configured == want
