"""Seeded weights for the benchmark, in the program's parameter layout.

The benchmark makes the weights, not the program: the same tree feeds the
program (through the experiment's models) and the plain reference, so the
reference takes nothing the program made.  The layout and each matrix's
fan-in come from the architecture's family (``chipbench/archs``);
``cell.model_config`` checks the layout against the program's own
``init_params`` shapes, so a change of layout fails loudly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _draw(key, path: str, sd: jax.ShapeDtypeStruct, family, arch):
    shape = sd.shape
    if path.endswith("['scale']"):  # norm gains near 1
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif path.endswith("['b']"):  # projection biases
        x = 0.1 * jax.random.normal(key, shape, jnp.float32)
    else:
        fan_in = family.fan_in(arch, path, shape)
        x = jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                        jnp.float32) * fan_in ** -0.5
    return x.astype(sd.dtype)


def generate(key, family, arch, head: str) -> dict:
    """One model's weights from ``key`` (trace under ``jax.jit``)."""
    tree = family.layout(arch, head)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = [_draw(jax.random.fold_in(key, i), jax.tree_util.keystr(p), sd,
                 family, arch)
           for i, (p, sd) in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def make(key, family, arch) -> dict:
    """Policy ("lm") and value-model ("value") weights in one jitted call,
    in the type they are served in."""
    return jax.jit(lambda k: {
        "lm": generate(jax.random.fold_in(k, 0), family, arch, "lm"),
        "value": generate(jax.random.fold_in(k, 1), family, arch,
                          "value")})(key)


def seed_key(seed: int):
    """A PRNG key that depends on every bit of a seed of up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)
