"""Seeded weights for the benchmark, in the program's parameter layout.

The benchmark makes the weights, not the program: the same tree feeds the
program (through the experiment's models) and the plain reference, so the
reference takes nothing the program made.  The layout is written out here
from the configuration; ``cell.build`` checks it against the program's own
``init_params`` shapes, so a change of layout fails loudly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.arch import Arch


def layout(arch: Arch, head: str) -> dict:
    """ShapeDtypeStruct tree of one model (``head`` is "lm" or "value")."""
    d, f, n = arch.hidden_size, arch.intermediate_size, arch.num_hidden_layers
    q, kv, hd = arch.q_dim, arch.kv_dim, arch.head_dim
    dt = jnp.dtype(arch.dtype)

    def s(*shape, dtype=dt):
        return jax.ShapeDtypeStruct(shape, dtype)

    def dense(i, o, bias=False):
        p = {"w": s(n, i, o)}
        if bias:
            p["b"] = s(n, o)
        return p

    bias = arch.qkv_bias
    mixer = {"wq": dense(d, q, bias), "wk": dense(d, kv, bias),
             "wv": dense(d, kv, bias), "wo": dense(q, d)}
    if arch.qk_norm:
        mixer["q_norm"] = {"scale": s(n, hd)}
        mixer["k_norm"] = {"scale": s(n, hd)}
    block = {"ln1": {"scale": s(n, d)}, "mixer": mixer,
             "ln2": {"scale": s(n, d)},
             "ffn": {"w_gate": dense(d, f), "w_in": dense(d, f),
                     "w_out": dense(f, d)}}
    tree = {"embed": {"table": s(arch.vocab_size, d)},
            "groups": [{"b0": block}],
            "final_norm": {"scale": s(d)}}
    if head == "value":
        tree["value_head"] = {"w": s(d, 1, dtype=jnp.float32)}
    return tree


def _draw(key, path: str, sd: jax.ShapeDtypeStruct, arch: Arch):
    shape = sd.shape
    if path.endswith("['scale']"):  # norm gains near 1
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif path.endswith("['b']"):  # projection biases
        x = 0.1 * jax.random.normal(key, shape, jnp.float32)
    else:
        # matrices at fan_in**-0.5; the tied table is also the LM head,
        # so it is drawn at d_model**-0.5 and logits have unit scale
        fan_in = arch.hidden_size if "table" in path else shape[-2]
        x = jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                        jnp.float32) * fan_in ** -0.5
    return x.astype(sd.dtype)


def generate(key, arch: Arch, head: str) -> dict:
    """One model's weights from ``key`` (trace under ``jax.jit``)."""
    tree = layout(arch, head)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = [_draw(jax.random.fold_in(key, i), jax.tree_util.keystr(p), sd, arch)
           for i, (p, sd) in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def make(key, arch: Arch) -> dict:
    """Policy ("lm") and value-model ("value") weights in one jitted call,
    in the type they are served in."""
    return jax.jit(lambda k: {
        "lm": generate(jax.random.fold_in(k, 0), arch, "lm"),
        "value": generate(jax.random.fold_in(k, 1), arch, "value")})(key)


def seed_key(seed: int):
    """A PRNG key that depends on every bit of a seed of up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)
