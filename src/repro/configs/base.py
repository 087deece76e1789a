"""Unified model configuration covering every assigned architecture family.

A model is a sequence of *scan groups*: an optional ``lead`` group (layers
ahead of the repeated pattern, such as DeepSeek's leading dense layer),
``superblock`` repeated ``n_superblocks`` times (stacked + ``lax.scan``-ed),
and an optional ``tail`` group.  Every layer inside a superblock is one mixer
(attention / RG-LRU / Mamba2-SSD) plus an optional FFN, so heterogeneous
patterns (Gemma-3 5:1 local:global, RecurrentGemma 2:1 lru:attn) scan cleanly.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

ATTN = "attn"
LRU = "lru"
SSM = "ssm"


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One mixer layer inside a superblock."""

    kind: str = ATTN  # attn | lru | ssm
    window: Optional[int] = None  # sliding-window size; None => full causal
    has_ffn: bool = True
    # the gated MLP of width ``cfg.d_ff`` even in an MoE model (DeepSeek's
    # leading dense layers)
    dense_ffn: bool = False

    @property
    def is_local(self) -> bool:
        return self.window is not None


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | encdec
    num_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int

    superblock: tuple[LayerSpec, ...]
    n_superblocks: int
    tail: tuple[LayerSpec, ...] = ()
    lead: tuple[LayerSpec, ...] = ()  # one group ahead of the superblocks

    # FFN flavour
    ffn_kind: str = "gated"  # gated | moe | none
    n_experts: int = 0
    top_k: int = 0
    expert_d_ff: int = 0
    dense_residual_ffn: bool = False  # Arctic: dense MLP in parallel with MoE
    # DeepSeekMoE: shared experts, a SwiGLU of n_shared_experts *
    # expert_d_ff that every token goes through
    n_shared_experts: int = 0
    # renormalise the top-k router weights over the row's k experts
    # (granite, arctic); DeepSeek-V2 keeps the softmax probabilities
    norm_topk_prob: bool = True
    # Expert parallelism: the layer holds experts [expert_offset,
    # expert_offset + n_local_experts) of the router's n_experts and
    # computes only their part of the result; 0 holds them all
    n_local_experts: int = 0
    expert_offset: int = 0
    # MoE dispatch mode: "dropless" (cohort-independent grouped dispatch —
    # decode bit-matches the training forward) or "capacity" (legacy (E, C, D)
    # capacity-drop buffers, kept for training-parity experiments).
    moe_dispatch: str = "dropless"

    # Attention details
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 1e4
    act: str = "silu"  # silu | gelu

    # Multi-head latent attention (DeepSeek-V2), on when kv_lora_rank > 0:
    # K/V come from a kv_lora_rank-wide latent, RoPE from a separate
    # qk_rope_head_dim key shared by all heads; head_dim is then
    # qk_nope_head_dim + qk_rope_head_dim (the q/k width)
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN RoPE scaling, on when yarn_factor > 1
    yarn_factor: float = 0.0
    yarn_orig_max_pos: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    # Mamba2 / SSD
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv: int = 4
    ssm_chunk: int = 128

    # RG-LRU
    lru_width: int = 0

    # Encoder-decoder (seamless): encoder layers use bidirectional attention,
    # decoder layers add cross-attention.  num_layers == decoder layers.
    enc_layers: int = 0

    # Modality stub: number of precomputed prefix embeddings (vlm patches /
    # audio frames) provided by input_specs() instead of token ids.
    prefix_len: int = 0

    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"

    # ---------------------------------------------------------------- sanity
    def __post_init__(self):
        n = (len(self.lead) + len(self.superblock) * self.n_superblocks
             + len(self.tail))
        assert n == self.num_layers, (
            f"{self.name}: pattern covers {n} layers != num_layers={self.num_layers}")
        if self.family != "encdec":
            assert self.enc_layers == 0
        assert self.moe_dispatch in ("dropless", "capacity"), self.moe_dispatch
        if self.kv_lora_rank:
            assert self.head_dim == (self.qk_nope_head_dim
                                     + self.qk_rope_head_dim), self.name
        # YaRN's cos/sin scale mscale / mscale_all_dim is 1 (DeepSeek-V2);
        # its temperature enters the softmax scale (models/mla.py)
        assert self.yarn_factor <= 1 or (
            self.yarn_mscale == self.yarn_mscale_all_dim), self.name
        assert 0 <= self.expert_offset and (
            self.expert_offset + self.local_experts <= self.n_experts), \
            f"{self.name}: held experts beyond the router's {self.n_experts}"

    # ------------------------------------------------------------ properties
    @property
    def layers(self) -> list[LayerSpec]:
        return (list(self.lead) + list(self.superblock) * self.n_superblocks
                + list(self.tail))

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def local_experts(self) -> int:
        """Routed experts this layer holds."""
        return self.n_local_experts or self.n_experts

    @property
    def shared_d_ff(self) -> int:
        return self.n_shared_experts * self.expert_d_ff

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def ssm_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.ssm_inner // self.ssm_head_dim if self.ssm_state else 0

    @property
    def is_subquadratic(self) -> bool:
        """True if the arch can serve 500k-token contexts without a full
        quadratic KV cache on every layer (SSM / hybrid / mostly-local attn)."""
        specs = self.layers
        n_full = sum(1 for s in specs if s.kind == ATTN and s.window is None)
        return n_full <= len(specs) // 4

    # --------------------------------------------------------- param counts
    def attn_params(self, spec: LayerSpec) -> int:
        d, q, kv = self.d_model, self.q_dim, self.kv_dim
        if self.is_mla:
            r, h = self.kv_lora_rank, self.n_heads
            return (d * q + d * (r + self.qk_rope_head_dim) + r
                    + r * h * (self.qk_nope_head_dim + self.v_head_dim)
                    + h * self.v_head_dim * d)
        p = d * q + 2 * d * kv + q * d  # wq, wk, wv, wo
        if self.qkv_bias:
            p += q + 2 * kv
        if self.qk_norm:
            p += 2 * self.head_dim
        return p

    def ffn_params(self, active_only: bool = False,
                   dense: bool = False) -> int:
        d = self.d_model
        if self.ffn_kind == "none":
            return 0
        if self.ffn_kind == "moe" and not dense:
            per_expert = 3 * d * self.expert_d_ff
            n = self.top_k if active_only else self.local_experts
            p = n * per_expert + d * self.n_experts  # experts + router
            if self.dense_residual_ffn:
                p += 3 * d * self.d_ff
            return p + 3 * d * self.shared_d_ff
        return 3 * d * self.d_ff  # gated: w_in, w_gate, w_out

    def lru_params(self) -> int:
        d, w = self.d_model, self.lru_width
        conv = 4 * w  # temporal conv1d width 4
        return 2 * d * w + w * d + conv + 2 * w  # in/gate proj, out proj, a/gate params

    def ssm_params(self) -> int:
        d, di, ds = self.d_model, self.ssm_inner, self.ssm_state
        in_proj = d * (2 * di + 2 * ds + self.ssm_heads)  # x, z, B, C, dt
        conv = self.ssm_conv * (di + 2 * ds)
        out = di * d
        extra = 2 * self.ssm_heads + di  # A_log, D, norm
        return in_proj + conv + out + extra

    def layer_params(self, spec: LayerSpec, active_only: bool = False) -> int:
        norms = 2 * self.d_model
        if spec.kind == ATTN:
            p = self.attn_params(spec)
        elif spec.kind == LRU:
            p = self.lru_params()
        else:
            p = self.ssm_params()
        if spec.has_ffn and self.ffn_kind != "none":
            p += self.ffn_params(active_only, spec.dense_ffn) + self.d_model
        return p + norms

    def param_count(self, active_only: bool = False) -> int:
        p = sum(self.layer_params(s, active_only) for s in self.layers)
        p += self.vocab_size * self.d_model  # input embedding
        if not self.tie_embeddings:
            p += self.vocab_size * self.d_model  # lm head
        p += self.d_model  # final norm
        if self.family == "encdec":
            enc_spec = LayerSpec(ATTN, None, True)
            xattn = self.d_model * self.q_dim + 2 * self.d_model * self.kv_dim + \
                self.q_dim * self.d_model + self.d_model
            p += self.enc_layers * self.layer_params(enc_spec, active_only)
            p += self.num_layers * xattn  # decoder cross-attn
            p += self.d_model
        return p

    def active_param_count(self) -> int:
        return self.param_count(active_only=True)

    # ----------------------------------------------------------- reductions
    def reduced(self, **overrides) -> "ModelConfig":
        """A tiny config of the same family for CPU smoke tests."""
        n_sb = min(self.n_superblocks, 2)
        tail = self.tail
        num_layers = len(self.lead) + len(self.superblock) * n_sb + len(tail)
        head_dim = 16
        n_heads = min(self.n_heads, 4)
        n_kv = max(1, min(self.n_kv_heads, 2))
        d_model = 64
        mla = {}
        if self.is_mla:  # every head has its own K/V, from a 32-wide latent
            n_kv = n_heads
            mla = dict(kv_lora_rank=32, qk_nope_head_dim=16,
                       qk_rope_head_dim=8, v_head_dim=16, head_dim=24)
        kw = dict(
            name=self.name + "-smoke",
            num_layers=num_layers,
            n_superblocks=n_sb,
            d_model=d_model,
            n_heads=n_heads,
            n_kv_heads=n_kv,
            head_dim=head_dim,
            d_ff=128 if self.d_ff else 0,
            expert_d_ff=32 if self.expert_d_ff else 0,
            n_experts=min(self.n_experts, 4),
            top_k=min(self.top_k, 2),
            vocab_size=512,
            lru_width=64 if self.lru_width else 0,
            ssm_state=16 if self.ssm_state else 0,
            ssm_head_dim=16 if self.ssm_state else 64,
            ssm_chunk=8 if self.ssm_state else 128,
            enc_layers=min(self.enc_layers, 2),
            prefix_len=min(self.prefix_len, 8),
            superblock=tuple(
                dataclasses.replace(s, window=min(s.window, 16) if s.window else None)
                for s in self.superblock),
            tail=tuple(
                dataclasses.replace(s, window=min(s.window, 16) if s.window else None)
                for s in self.tail),
            n_local_experts=0,
            expert_offset=0,
            dtype="float32",  # CPU smoke tests run in fp32
        )
        kw.update(mla)
        kw.update(overrides)
        return dataclasses.replace(self, **kw)


def dense_pattern(n: int, window: Optional[int] = None) -> dict:
    return dict(superblock=(LayerSpec(ATTN, window),), n_superblocks=n, tail=())
