"""Architecture families behind one interface (``chipbench/archs``).

Every configuration file's family states the program's ``ModelConfig`` and
parameter layout exactly, checked here on the CPU as ``cell.build`` checks
them on the chip.  A second family that exists only for these tests
(``testdata/archs/dense_untied.py``: an untied head, and a leading layer of
its own FFN width ahead of the scanned ones) goes through the same loader
and the same generic weights, reference and roofline code, none of which
names it.
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import archs, flops
from chipbench import cell as C
from chipbench import weights as W
from chipbench.reference import Reference, follow

HERE = Path(__file__).resolve().parent
CONFIGS = sorted((HERE / "configs").glob("*.json"))
TEST_ARCHS = HERE / "testdata" / "archs"
UNTIED = {"hidden_size": 64, "intermediate_size": 128,
          "first_intermediate_size": 192, "num_hidden_layers": 3,
          "num_attention_heads": 4, "num_key_value_heads": 2,
          "head_dim": 16, "vocab_size": 512, "rope_theta": 1e6,
          "rms_norm_eps": 1e-6, "torch_dtype": "bfloat16",
          "qkv_bias": True, "qk_norm": True}
SEED = 2 ** 41 + 777


def _config(path):
    with open(path) as f:
        return json.load(f)


# ------------------------------------------------- the benchmark's files

@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_family_states_the_program(path):
    from repro.models import model as MDL
    config = _config(path)
    cfg = C.model_config(config)  # the harness's own check, as on the chip
    family = C.family_of(config)
    a = family.Arch.from_file(config)
    stated = family.stated(a)
    assert {k: getattr(cfg, k) for k in stated} == stated
    for head in ("lm", "value"):
        want = jax.eval_shape(lambda: MDL.init_params(  # noqa: B023
            jax.random.PRNGKey(0), cfg, head=head))
        assert family.layout(a, head) == want, head


def test_program_config_that_differs_is_refused():
    config = _config(HERE / "configs" / "qwen2-0.5b.json")
    config["num_key_value_heads"] = 7
    with pytest.raises(SystemExit, match="n_kv_heads"):
        C.model_config(config)


@pytest.mark.parametrize("family", [None, "moe_nowhere"])
def test_unknown_family_lists_the_known(family):
    config = {"family": family} if family else {}
    with pytest.raises(ValueError, match="'dense'"):
        C.family_of(config)


# ------------------------------------------ a family the harness never names

@pytest.fixture(scope="module")
def untied():
    family = archs.load("dense_untied", TEST_ARCHS)
    arch = family.Arch.from_file(UNTIED)
    key = jax.random.fold_in(W.seed_key(SEED), 1)
    return family, arch, W.make(key, family, arch), key


def test_untied_loads_from_its_directory_only():
    assert "dense_untied" not in archs.known()
    assert archs.load("dense_untied", TEST_ARCHS) is \
        archs.load("dense_untied", TEST_ARCHS)


def test_untied_layout_and_weights(untied):
    family, arch, w, _ = untied
    lm, val = w["lm"], w["value"]
    first, rest = (g["b0"] for g in lm["groups"])
    assert first["ffn"]["w_in"]["w"].shape == (1, 64, 192)
    assert rest["ffn"]["w_in"]["w"].shape == (2, 64, 128)
    assert lm["lm_head"]["w"].shape == (64, 512)
    assert "lm_head" not in val and "value_head" in val
    shapes = lambda t: jax.tree.map(lambda x: (x.shape, x.dtype), t)  # noqa: E731
    for head, tree in w.items():
        assert shapes(tree) == shapes(family.layout(arch, head)), head
        count = sum(x.size for x in jax.tree.leaves(tree))
        assert count == arch.param_count(head), head
    # drawn at d_model**-0.5 (a truncated normal's std is 0.88 of its scale)
    std = float(jnp.std(lm["lm_head"]["w"].astype(jnp.float32)))
    assert std == pytest.approx(0.88 * 64 ** -0.5, rel=0.05)
    assert np.linalg.norm(np.asarray(lm["lm_head"]["w"], np.float32).T
                          - np.asarray(lm["embed"]["table"], np.float32)) > 1


def test_untied_reference_reads_its_head(untied):
    family, arch, w, _ = untied
    hp = C.load_cell("qwen2-0.5b.ppo-b8-p128-g512").hp
    ref = Reference(family, arch, hp, 8)
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        0, 512, (4, 16), dtype=np.int32))
    with jax.default_matmul_precision("highest"):
        lp = np.asarray(ref.logprobs(w["lm"], tokens))
        v = np.asarray(ref.values(w["value"], tokens))
        p = jax.tree.map(lambda x: x.astype(jnp.float32), w["lm"])
        hid = family.forward(p, arch, tokens, jnp.einsum)[:, 7:-1]
        logits = hid @ p["lm_head"]["w"]
        tied = hid @ p["embed"]["table"].T
    want = np.take_along_axis(np.asarray(jax.nn.log_softmax(logits)),
                              np.asarray(tokens)[:, 8:, None], -1)[..., 0]
    np.testing.assert_allclose(lp, want, atol=1e-4)
    other = np.take_along_axis(np.asarray(jax.nn.log_softmax(tied)),
                               np.asarray(tokens)[:, 8:, None], -1)[..., 0]
    assert np.max(np.abs(lp - other)) > 0.1
    assert v.shape == (4, 9) and np.isfinite(v).all()


def test_untied_reference_trains_every_leaf(untied):
    family, arch, _, key = untied
    hp = C.load_cell("qwen2-0.5b.ppo-b8-p128-g512").hp
    rng = np.random.default_rng(5)
    seqs = [rng.integers(0, 512, (8, 16), dtype=np.int32) for _ in range(2)]
    r = follow(Reference(family, arch, hp, 8),
               lambda: W.make(key, family, arch), seqs)
    assert all(np.isfinite(x) for x in r["actor_loss"] + r["critic_loss"])
    for model in ("actor", "critic"):
        assert set(r[f"{model}_m"]) == set(r[f"{model}_change"])
    assert r["actor_m"]["['lm_head']['w']"] > 0
    assert r["actor_change"]["['lm_head']['w']"] > 0
    first = "['groups'][0]['b0']['ffn']['w_in']['w']"
    assert r["actor_change"][first] > 0 and r["critic_change"][first] > 0


def test_untied_calls_against_a_hand_count(untied):
    family, arch, _, _ = untied
    dense = archs.load("dense")
    twin = dense.Arch(**{f.name: getattr(arch, f.name)
                         for f in dataclasses.fields(dense.Arch)})
    b, p, g, m = 2, 8, 8, 2
    got = family.calls(arch, b, p, g, m)
    base = dense.calls(twin, b, p, g, m)
    extra = 3 * 64 * (192 - 128)  # the leading layer's wider FFN
    head = 512 * 64  # the untied head, in the policy only
    per_param = 30  # bytes an AdamW step moves per parameter
    want = {
        "actor_gen": (2 * extra * b * (p + g - 1), g * 2 * (extra + head)),
        "ref_inf": (2 * extra * b * (p + g), 2 * (extra + head)),
        "reward_inf": (2 * extra * b * (p + g), 2 * extra),
        "critic_inf": (2 * extra * b * (p + g), 2 * extra),
        "actor_train": (3 * 2 * extra * b * (p + g),
                        m * (extra + head) * per_param),
        "critic_train": (3 * 2 * extra * b * (p + g), m * extra * per_param),
    }
    assert set(got) == set(want)
    for call, (d_flops, d_bytes) in want.items():
        assert got[call]["flops"] - base[call]["flops"] == d_flops, call
        assert got[call]["bytes"] - base[call]["bytes"] == d_bytes, call
    with open(HERE / "peaks.json") as f:
        peak = json.load(f)["TPU v5 lite"]
    seconds, bound = flops.least_seconds(got["actor_train"], peak)
    assert seconds > 0 and bound in ("flops", "bytes")
