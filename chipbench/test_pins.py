"""The dense family gives what the harness gave before its architecture code
moved behind ``chipbench/archs``: the seeded weights, the reference's
logprobs, values and followed iterations bit for bit on the CPU, and every
cell's FLOP, byte and parameter counts number for number.

``testdata/dense_pins.json`` holds the values recorded from the harness as
it was, by the same computations as below.
"""

import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import archs
from chipbench import cell as C
from chipbench import weights as W
from chipbench.reference import Reference, follow

HERE = Path(__file__).resolve().parent
with open(HERE / "testdata" / "dense_pins.json") as f:
    PINS = json.load(f)
with open(HERE.parent / "BENCHMARK.json") as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]

TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            vocab_size=512, rope_theta=1e6, rms_norm_eps=1e-6,
            torch_dtype="bfloat16")
TINY_ARCHS = {"bias": dict(TINY, qkv_bias=True, qk_norm=False),
              "qk_norm": dict(TINY, qkv_bias=False, qk_norm=True)}
SEED = 2 ** 40 + 12345
GEN_START = 8


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = np.asarray(jax.device_get(x))
        h.update(f"{jax.tree_util.keystr(path)} {x.dtype} {x.shape}".encode())
        h.update(x.tobytes())
    return h.hexdigest()


def tiny(name):
    dense = archs.load("dense")
    arch = dense.Arch.from_file(TINY_ARCHS[name])
    key = jax.random.fold_in(W.seed_key(SEED), 1)
    return dense, arch, key


def sequences():
    rng = np.random.default_rng(7)
    return [rng.integers(0, 512, (8, 16), dtype=np.int32) for _ in range(2)]


def hp():
    return C.load_cell("qwen2-0.5b.ppo-b8-p128-g512").hp


@pytest.mark.parametrize("name", sorted(TINY_ARCHS))
def test_weights_as_pinned(name):
    dense, arch, key = tiny(name)
    assert digest(W.make(key, dense, arch)) == PINS["weights"][name]


@pytest.mark.parametrize("dot", ["fp32", "fp8"])
@pytest.mark.parametrize("name", sorted(TINY_ARCHS))
def test_reference_outputs_as_pinned(name, dot):
    dense, arch, key = tiny(name)
    ref = Reference(dense, arch, hp(), GEN_START, dot=dot)
    with jax.default_matmul_precision("highest"):
        w = W.make(key, dense, arch)
        t = jnp.asarray(sequences()[0])
        lp = ref.logprobs(w["lm"], t)
        v = ref.values(w["value"], t)
    assert digest(lp) == PINS["reference"][f"{name}.{dot}.logprobs"]
    assert digest(v) == PINS["reference"][f"{name}.{dot}.values"]


@pytest.mark.parametrize("name", sorted(TINY_ARCHS))
def test_followed_iterations_as_pinned(name):
    dense, arch, key = tiny(name)
    r = follow(Reference(dense, arch, hp(), GEN_START),
               lambda: W.make(key, dense, arch), sequences())
    assert digest(r) == PINS["reference"][f"{name}.follow"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_counts_as_pinned(name):
    cell = C.load_cell(name)
    assert cell.costs == PINS["calls"][name]
    assert {h: cell.arch.param_count(h) for h in ("lm", "value")} == \
        PINS["param_count"][name]
