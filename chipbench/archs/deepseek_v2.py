"""DeepSeek-V2: multi-head latent attention (MLA) with YaRN RoPE, leading
dense layers, then DeepSeekMoE layers (softmax top-k over the routed
experts, optionally without renormalising, plus shared experts).  Untied
head.

Stated from a configuration file's published keys (Hugging Face
``config.json`` names, DeepSeek's ``modeling_deepseek.py`` equations),
independent of the program's own ``ModelConfig``.  The interface is
described in ``chipbench/archs/__init__.py``.

A configuration may hold a share of the routed experts, as one chip of an
expert-parallel layer does: ``n_routed_experts`` is then the count held
here, ``expert_offset`` the first held expert, and the router keeps the
published width (``published["n_routed_experts"]``).  Assignments to
experts that are not held add nothing; the shared experts and everything
outside the expert layer are whole.

The reference computes MLA in its expanded form only (K and V of every
head built from the latent).  RoPE pairs dims (i, i + d/2) (rotate-half)
over the 64 rope dims; DeepSeek's code de-interleaves them first, which
differs by a fixed permutation of the rope columns of ``q_proj`` and
``kv_a_proj_with_mqa`` and matters only for published weights.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

BF16 = 2
F32 = 4


@dataclasses.dataclass(frozen=True)
class Arch:
    hidden_size: int
    intermediate_size: int  # the leading dense layers' FFN
    moe_intermediate_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    num_attention_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_routed_experts: int  # held here
    router_experts: int  # the router's width
    expert_offset: int
    num_experts_per_tok: int
    n_shared_experts: int
    norm_topk_prob: bool
    vocab_size: int
    rope_theta: float
    yarn_factor: float
    yarn_orig_max_pos: int
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_mscale: float
    yarn_mscale_all_dim: float
    rms_norm_eps: float
    dtype: str = "bfloat16"

    @classmethod
    def from_file(cls, cfg: dict) -> "Arch":
        rs = cfg["rope_scaling"]
        fixed = {"rope_scaling.type": (rs["type"], "yarn"),
                 "q_lora_rank": (cfg["q_lora_rank"], None),
                 "scoring_func": (cfg["scoring_func"], "softmax"),
                 "topk_method": (cfg["topk_method"], "greedy"),
                 "routed_scaling_factor": (cfg["routed_scaling_factor"], 1),
                 "moe_layer_freq": (cfg["moe_layer_freq"], 1),
                 "tie_word_embeddings": (cfg["tie_word_embeddings"], False)}
        other = {k: v for k, (v, want) in fixed.items() if v != want}
        if other:
            raise ValueError(f"the family implements only {fixed}; the file "
                             f"has {other}")
        return cls(
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            num_hidden_layers=cfg["num_hidden_layers"],
            first_k_dense_replace=cfg["first_k_dense_replace"],
            num_attention_heads=cfg["num_attention_heads"],
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
            n_routed_experts=cfg["n_routed_experts"],
            router_experts=cfg.get("published", {}).get(
                "n_routed_experts", cfg["n_routed_experts"]),
            expert_offset=cfg.get("expert_offset", 0),
            num_experts_per_tok=cfg["num_experts_per_tok"],
            n_shared_experts=cfg["n_shared_experts"],
            norm_topk_prob=bool(cfg["norm_topk_prob"]),
            vocab_size=cfg["vocab_size"],
            rope_theta=float(cfg["rope_theta"]),
            yarn_factor=float(rs["factor"]),
            yarn_orig_max_pos=rs["original_max_position_embeddings"],
            yarn_beta_fast=float(rs["beta_fast"]),
            yarn_beta_slow=float(rs["beta_slow"]),
            yarn_mscale=float(rs["mscale"]),
            yarn_mscale_all_dim=float(rs["mscale_all_dim"]),
            rms_norm_eps=float(cfg["rms_norm_eps"]),
            dtype=cfg["torch_dtype"])

    # ---------------------------------------------------------- widths
    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Values a token caches per layer: the latent and the rope key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def shared_intermediate_size(self) -> int:
        return self.n_shared_experts * self.moe_intermediate_size

    @property
    def moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    # ------------------------------------------------------- parameters
    def mla_params(self) -> int:
        """One layer's attention: q_proj, kv_a_proj_with_mqa,
        kv_a_layernorm, kv_b_proj, o_proj."""
        d, h, r = self.hidden_size, self.num_attention_heads, self.kv_lora_rank
        return (d * h * self.q_head_dim + d * self.latent_dim + r
                + r * h * (self.qk_nope_head_dim + self.v_head_dim)
                + h * self.v_head_dim * d)

    def expert_params(self) -> int:
        return 3 * self.hidden_size * self.moe_intermediate_size

    def shared_params(self) -> int:
        return 3 * self.hidden_size * self.shared_intermediate_size

    def router_params(self) -> int:
        return self.hidden_size * self.router_experts

    def dense_params(self) -> int:
        return 3 * self.hidden_size * self.intermediate_size

    def moe_params(self) -> int:
        """One expert layer as held here."""
        return (self.n_routed_experts * self.expert_params()
                + self.shared_params() + self.router_params())

    def param_count(self, head: str = "lm") -> int:
        d, v = self.hidden_size, self.vocab_size
        norms = 2 * d
        p = (self.num_hidden_layers * (self.mla_params() + norms)
             + self.first_k_dense_replace * self.dense_params()
             + self.moe_layers * self.moe_params())
        p += v * d + d  # embedding, final norm
        return p + (v * d if head == "lm" else d)


def stated(a: Arch) -> dict:
    """The program ``ModelConfig`` fields the file fixes."""
    return {"d_model": a.hidden_size, "d_ff": a.intermediate_size,
            "num_layers": a.num_hidden_layers,
            "n_superblocks": a.moe_layers,
            "n_heads": a.num_attention_heads,
            "n_kv_heads": a.num_attention_heads, "head_dim": a.q_head_dim,
            "kv_lora_rank": a.kv_lora_rank,
            "qk_nope_head_dim": a.qk_nope_head_dim,
            "qk_rope_head_dim": a.qk_rope_head_dim,
            "v_head_dim": a.v_head_dim,
            "n_experts": a.router_experts,
            "n_local_experts": a.n_routed_experts,
            "expert_offset": a.expert_offset,
            "top_k": a.num_experts_per_tok,
            "expert_d_ff": a.moe_intermediate_size,
            "n_shared_experts": a.n_shared_experts,
            "norm_topk_prob": a.norm_topk_prob,
            "vocab_size": a.vocab_size, "rope_theta": a.rope_theta,
            "yarn_factor": a.yarn_factor,
            "yarn_orig_max_pos": a.yarn_orig_max_pos,
            "yarn_beta_fast": a.yarn_beta_fast,
            "yarn_beta_slow": a.yarn_beta_slow,
            "yarn_mscale": a.yarn_mscale,
            "yarn_mscale_all_dim": a.yarn_mscale_all_dim,
            "norm_eps": a.rms_norm_eps, "dtype": a.dtype,
            "tie_embeddings": False, "family": "moe", "ffn_kind": "moe",
            "moe_dispatch": "dropless", "act": "silu"}


# ------------------------------------------------------------- weights

def block_layout(a: Arch, n: int, moe: bool) -> dict:
    """One scanned group of ``n`` layers: MLA, then the dense FFN or the
    expert layer."""
    d, h, r = a.hidden_size, a.num_attention_heads, a.kv_lora_rank
    dt = jnp.dtype(a.dtype)

    def s(*shape, dtype=dt):
        return jax.ShapeDtypeStruct(shape, dtype)

    def mlp(width):
        return {"w_gate": {"w": s(n, d, width)}, "w_in": {"w": s(n, d, width)},
                "w_out": {"w": s(n, width, d)}}

    mixer = {"wq": {"w": s(n, d, h * a.q_head_dim)},
             "wkv_a": {"w": s(n, d, a.latent_dim)},
             "kv_norm": {"scale": s(n, r)},
             "wkv_b": {"w": s(n, r, h * (a.qk_nope_head_dim + a.v_head_dim))},
             "wo": {"w": s(n, h * a.v_head_dim, d)}}
    if moe:
        e, f = a.n_routed_experts, a.moe_intermediate_size
        ffn = {"router": {"w": s(n, d, a.router_experts, dtype=jnp.float32)},
               "w_gate": s(n, e, d, f), "w_in": s(n, e, d, f),
               "w_out": s(n, e, f, d),
               "shared": mlp(a.shared_intermediate_size)}
    else:
        ffn = mlp(a.intermediate_size)
    return {"ln1": {"scale": s(n, d)}, "mixer": mixer,
            "ln2": {"scale": s(n, d)}, "ffn": ffn}


def layout(a: Arch, head: str) -> dict:
    """ShapeDtypeStruct tree of one model (``head`` is "lm" or "value"):
    the leading dense layers as one group, the expert layers as another."""
    d = a.hidden_size
    dt = jnp.dtype(a.dtype)
    groups = []
    if a.first_k_dense_replace:
        groups.append({"b0": block_layout(a, a.first_k_dense_replace, False)})
    groups.append({"b0": block_layout(a, a.moe_layers, True)})
    tree = {"embed": {"table": jax.ShapeDtypeStruct((a.vocab_size, d), dt)},
            "groups": groups,
            "final_norm": {"scale": jax.ShapeDtypeStruct((d,), dt)}}
    if head == "lm":
        tree["lm_head"] = {"w": jax.ShapeDtypeStruct((d, a.vocab_size), dt)}
    else:
        tree["value_head"] = {"w": jax.ShapeDtypeStruct((d, 1), jnp.float32)}
    return tree


def fan_in(a: Arch, path: str, shape) -> int:
    """The table and the head at d_model**-0.5, so logits have unit scale;
    every other matrix (the experts' stacked too) at its fan-in."""
    if path.startswith(("['embed']", "['lm_head']")):
        return a.hidden_size
    return shape[-2]


# ----------------------------------------------------------- reference

def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_correction_range(a: Arch) -> tuple[int, int]:
    """The first and last rotary pair of YaRN's ramp: pair i turns
    beta_fast (beta_slow) times over the original context at low (high)."""
    dim = a.qk_rope_head_dim

    def pair(turns):
        return (dim * math.log(a.yarn_orig_max_pos / (turns * 2 * math.pi))
                / (2 * math.log(a.rope_theta)))
    return (max(math.floor(pair(a.yarn_beta_fast)), 0),
            min(math.ceil(pair(a.yarn_beta_slow)), dim - 1))


def inv_freq(a: Arch) -> np.ndarray:
    """(rope_dim / 2,) YaRN frequencies: theta**(-2i/d) below the ramp,
    the same over the factor above it, linear in between."""
    dim = a.qk_rope_head_dim
    extra = a.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    low, high = yarn_correction_range(a)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return extra * (1 - ramp) + extra / a.yarn_factor * ramp


def softmax_scale(a: Arch) -> float:
    m = yarn_mscale(a.yarn_factor, a.yarn_mscale_all_dim)
    return a.q_head_dim ** -0.5 * m * m


def _rope(x, a: Arch):
    """Rotate-half RoPE at YaRN's frequencies over positions 0..S-1; x
    (B, S, H, d).  Cos and sin are scaled by mscale / mscale_all_dim."""
    freqs = jnp.asarray(inv_freq(a), jnp.float32)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    m = yarn_mscale(a.yarn_factor, a.yarn_mscale) / yarn_mscale(
        a.yarn_factor, a.yarn_mscale_all_dim)
    cos = (jnp.cos(ang) * m)[None, :, None]
    sin = (jnp.sin(ang) * m)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(y, f, dot):
    g = jax.nn.silu(dot("bsd,df->bsf", y, f["w_gate"]["w"]))
    return dot("bsf,fd->bsd", g * dot("bsd,df->bsf", y, f["w_in"]["w"]),
               f["w_out"]["w"])


def _experts(y, f, a: Arch, dot):
    """The held experts' part of the expert layer: the router scores all
    of its experts, each token keeps its top k, and each held expert adds
    its output at the token's routing weight for it (0 where it was not
    chosen).  One held expert at a time over every token."""
    logits = dot("bsd,de->bse", y, f["router"]["w"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, a.num_experts_per_tok)
    if a.norm_topk_prob:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    held = a.expert_offset + jnp.arange(a.n_routed_experts)
    # (B, S, E_held): the weight each token gives each held expert
    weight = jnp.sum(jnp.where(top_i[..., None, :] == held[:, None],
                               top_w[..., None, :], 0.0), axis=-1)

    def one(carry, e):
        wg, wi, wo, w = e
        g = jax.nn.silu(dot("bsd,df->bsf", y, wg)) * dot("bsd,df->bsf", y, wi)
        return carry + w[..., None] * dot("bsf,fd->bsd", g, wo), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(y),
        (f["w_gate"], f["w_in"], f["w_out"], jnp.moveaxis(weight, -1, 0)))
    return out


def forward(p, a: Arch, tokens, dot):
    """Final-norm hidden states (B, S, D) in float32; each entry of
    ``groups`` is scanned in turn, the expert layers' having a router."""
    b, s = tokens.shape
    h, r = a.num_attention_heads, a.kv_lora_rank
    dn, dr, dv = a.qk_nope_head_dim, a.qk_rope_head_dim, a.v_head_dim
    eps = a.rms_norm_eps
    scale = softmax_scale(a)
    causal = jnp.tril(jnp.ones((s, s), bool))
    x = p["embed"]["table"][tokens]

    def layer(x, lp):
        m = lp["mixer"]
        y = _rms(x, lp["ln1"]["scale"], eps)
        q = dot("bsd,df->bsf", y, m["wq"]["w"]).reshape(b, s, h, dn + dr)
        q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], a)
        kv = dot("bsd,df->bsf", y, m["wkv_a"]["w"])
        c = _rms(kv[..., :r], m["kv_norm"]["scale"], eps)
        k_pe = _rope(kv[..., None, r:], a)[:, :, 0]  # one key, every head
        kvb = dot("bsr,rf->bsf", c, m["wkv_b"]["w"]).reshape(b, s, h, dn + dv)
        k_nope, v = kvb[..., :dn], kvb[..., dn:]
        sc = (dot("bqhd,bkhd->bhqk", q_nope, k_nope)
              + dot("bqhd,bkd->bhqk", q_pe, k_pe)) * scale
        sc = jnp.where(causal, sc, -jnp.inf)
        att = dot("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v)
        x = x + dot("bsf,fd->bsd", att.reshape(b, s, h * dv), m["wo"]["w"])
        y = _rms(x, lp["ln2"]["scale"], eps)
        f = lp["ffn"]
        if "router" in f:
            return x + _experts(y, f, a, dot) + _swiglu(y, f["shared"], dot), \
                None
        return x + _swiglu(y, f, dot), None

    for group in p["groups"]:
        x, _ = jax.lax.scan(jax.checkpoint(layer), x, group["b0"])
    return _rms(x, p["final_norm"]["scale"], eps)


def lm_head(p):
    """The untied head, as the (V, D) matrix the logits use."""
    return p["lm_head"]["w"].T


# ------------------------------------------------------------ counting
# Model FLOPs: a matmul of an (m, k) by a (k, n) matrix is 2mkn.  The
# routed experts count at the share of assignments expected on the held
# experts under uniform routing (k * held / router width a token), the
# router, shared experts and everything else for every token.  Expanded
# attention (prefill, scoring, training) counts 2 * (q/k width + v width)
# FLOPs per (query, key) pair and head; the absorbed decode 2 * (latent +
# rope width) for the scores and 2 * latent width for the weighted sum per
# pair and head (its per-token absorption of kv_b_proj is in the trunk,
# where the expanded form applies kv_b_proj once per token too).  The LM
# head counts only the positions whose logprob is needed; remat does not
# count.  Bytes are the least traffic to HBM: weights read once per pass
# (in a decode step, a held expert's weights times the chance that any of
# the batch's tokens chose it under uniform routing), the latent cache read
# up to each decode step's length, and the optimizer's reads and writes.

def held_share(a: Arch) -> float:
    """Expected assignments a token sends to the held experts."""
    return a.num_experts_per_tok * a.n_routed_experts / a.router_experts


def token_matmul_flops(a: Arch) -> float:
    """The layers' matmul FLOPs a token needs, without attention."""
    per_moe = (2 * (a.router_params() + a.shared_params())
               + 2 * held_share(a) * a.expert_params())
    # kv_a_layernorm's gain is the one weight of MLA outside a matmul
    return (a.num_hidden_layers * 2 * (a.mla_params() - a.kv_lora_rank)
            + a.first_k_dense_replace * 2 * a.dense_params()
            + a.moe_layers * per_moe)


def attention_flops(a: Arch, pairs: int) -> int:
    """Expanded attention over ``pairs`` (query, key) pairs."""
    per_pair = 2 * (a.q_head_dim + a.v_head_dim) * a.num_attention_heads
    return per_pair * a.num_hidden_layers * pairs


def absorbed_attention_flops(a: Arch, pairs: int) -> int:
    """Absorbed decode attention over ``pairs`` (token, cached) pairs."""
    per_pair = 2 * (a.latent_dim + a.kv_lora_rank) * a.num_attention_heads
    return per_pair * a.num_hidden_layers * pairs


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def head_flops(a: Arch, positions: int, head: str) -> int:
    width = a.vocab_size if head == "lm" else 1
    return 2 * a.hidden_size * width * positions


def forward_flops(a: Arch, batch: int, seq: int, head: str,
                  head_positions: int) -> float:
    return (token_matmul_flops(a) * batch * seq
            + attention_flops(a, batch * causal_pairs(seq))
            + head_flops(a, batch * head_positions, head))


def weight_bytes(a: Arch, head: str = "lm") -> int:
    """bf16 weights (the routers' and value head's fp32 entries at 4)."""
    fp32 = a.moe_layers * a.router_params() + (
        a.hidden_size if head == "value" else 0)
    return (a.param_count(head) - fp32) * BF16 + fp32 * F32


def expert_hit_chance(a: Arch, batch: int) -> float:
    """Chance that one held expert gets any of ``batch`` tokens' top-k
    assignments, under uniform routing."""
    return 1.0 - (1.0 - a.num_experts_per_tok / a.router_experts) ** batch


def decode_weight_bytes(a: Arch, batch: int) -> float:
    """Weights one decode step of ``batch`` tokens reads."""
    held = a.moe_layers * a.n_routed_experts * a.expert_params() * BF16
    return (weight_bytes(a) - held
            + held * expert_hit_chance(a, batch))


def generate(a: Arch, batch: int, prompt: int, gen: int) -> dict:
    """Prefill of the prompt (head at its last position), then ``gen - 1``
    absorbed decode steps: step i feeds the token at position prompt + i,
    which attends to prompt + i + 1 cached positions."""
    ctx = [prompt + i + 1 for i in range(gen - 1)]
    flops = (forward_flops(a, batch, prompt, "lm", 1)
             + (gen - 1) * (token_matmul_flops(a) * batch
                            + head_flops(a, batch, "lm"))
             + absorbed_attention_flops(a, batch * sum(ctx)))
    cache = a.num_hidden_layers * a.latent_dim * BF16  # a token's
    nbytes = (weight_bytes(a) + (gen - 1) * decode_weight_bytes(a, batch)
              + batch * cache * (prompt + sum(ctx)))
    return {"flops": int(flops), "bytes": int(nbytes)}


def train_bytes(a: Arch, head: str, minibatches: int) -> int:
    """Per AdamW step: bf16 weights read by forward and backward and
    written once, fp32 gradients written and read, fp32 master and bf16
    moments read and written."""
    per_param = 3 * BF16 + 2 * 4 + 2 * 4 + 2 * 2 * BF16
    return minibatches * a.param_count(head) * per_param


def calls(a: Arch, batch: int, prompt: int, gen: int,
          minibatches: int) -> dict:
    """FLOPs and bytes of every call of one PPO iteration."""
    seq = prompt + gen
    lm = forward_flops(a, batch, seq, "lm", gen)
    val = forward_flops(a, batch, seq, "value", gen + 1)
    reward = forward_flops(a, batch, seq, "value", 1)
    return {
        "actor_gen": generate(a, batch, prompt, gen),
        "ref_inf": {"flops": int(lm), "bytes": weight_bytes(a)},
        "reward_inf": {"flops": int(reward),
                       "bytes": weight_bytes(a, "value")},
        "critic_inf": {"flops": int(val), "bytes": weight_bytes(a, "value")},
        "actor_train": {"flops": int(3 * lm),
                        "bytes": train_bytes(a, "lm", minibatches)},
        "critic_train": {"flops": int(3 * val),
                         "bytes": train_bytes(a, "value", minibatches)},
    }
