"""A family for the tests only: the dense decoder with an untied LM head
and a leading layer of its own FFN width ahead of the scanned layers, as
two entries of ``groups``.

It has the two structural differences from ``dense`` that a model with a
dense first layer ahead of its MoE layers and an untied head brings, and
shows that the generic code of the benchmark takes them as they come.  The
head is stored as the program stores an untied one, ``lm_head.w`` (D, V).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from chipbench import archs

dense = archs.load("dense")
forward = dense.forward
calls = dense.calls


@dataclasses.dataclass(frozen=True)
class Arch(dense.Arch):
    first_intermediate_size: int = 0

    @classmethod
    def from_file(cls, cfg: dict) -> "Arch":
        return dataclasses.replace(
            super().from_file(cfg),
            first_intermediate_size=cfg["first_intermediate_size"])

    def _first_extra(self) -> int:
        """Matmul weights the leading layer has beyond a scanned one."""
        return (3 * self.hidden_size
                * (self.first_intermediate_size - self.intermediate_size))

    def matmul_params(self) -> int:
        return super().matmul_params() + self._first_extra()

    def param_count(self, head: str = "lm") -> int:
        """The dense count, the leading layer's wider FFN and, in the
        policy, the untied head."""
        untied = self.vocab_size * self.hidden_size if head == "lm" else 0
        return super().param_count(head) + self._first_extra() + untied


def stated(a: Arch) -> dict:
    return dict(dense.stated(a), tie_embeddings=False)


def layout(arch: Arch, head: str) -> dict:
    tree = dense.layout(arch, head)
    n = arch.num_hidden_layers
    tree["groups"] = [
        {"b0": dense.block_layout(arch, 1, arch.first_intermediate_size)},
        {"b0": dense.block_layout(arch, n - 1, arch.intermediate_size)}]
    if head == "lm":
        tree["lm_head"] = {"w": jax.ShapeDtypeStruct(
            (arch.hidden_size, arch.vocab_size), jnp.dtype(arch.dtype))}
    return tree


def fan_in(arch: Arch, path: str, shape) -> int:
    """The table and the head at d_model**-0.5, so logits have unit scale;
    other matrices at their fan-in."""
    if path.startswith(("['embed']", "['lm_head']")):
        return arch.hidden_size
    return shape[-2]


def lm_head(p):
    return p["lm_head"]["w"].T
