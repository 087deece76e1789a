"""The dense decoder as the benchmark states it, from a configuration file's
published keys (Hugging Face ``config.json`` names), independent of the
program's own ``ModelConfig``."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Arch:
    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    vocab_size: int
    rope_theta: float
    rms_norm_eps: float
    qkv_bias: bool
    qk_norm: bool
    dtype: str = "bfloat16"

    @classmethod
    def from_file(cls, cfg: dict) -> "Arch":
        heads = cfg["num_attention_heads"]
        return cls(
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_hidden_layers=cfg["num_hidden_layers"],
            num_attention_heads=heads,
            num_key_value_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim", cfg["hidden_size"] // heads),
            vocab_size=cfg["vocab_size"],
            rope_theta=float(cfg["rope_theta"]),
            rms_norm_eps=float(cfg["rms_norm_eps"]),
            qkv_bias=bool(cfg["qkv_bias"]),
            qk_norm=bool(cfg["qk_norm"]),
            dtype=cfg["torch_dtype"])

    @property
    def q_dim(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_key_value_heads * self.head_dim

    def layer_matmul_params(self) -> int:
        """Weights of one layer that enter a matrix multiplication."""
        d = self.hidden_size
        return (2 * d * self.q_dim + 2 * d * self.kv_dim
                + 3 * d * self.intermediate_size)

    def layer_params(self) -> int:
        d = self.hidden_size
        p = self.layer_matmul_params() + 2 * d
        if self.qkv_bias:
            p += self.q_dim + 2 * self.kv_dim
        if self.qk_norm:
            p += 2 * self.head_dim
        return p

    def param_count(self, head: str = "lm") -> int:
        """Tied embedding: the table is the LM head too; a value model adds
        a (d, 1) head."""
        d = self.hidden_size
        p = (self.num_hidden_layers * self.layer_params()
             + self.vocab_size * d + d)
        return p + (d if head == "value" else 0)
