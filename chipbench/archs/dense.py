"""The dense decoder: tied embedding and LM head, GQA attention with optional
q/k/v biases and qk-norm, RoPE, RMSNorm and a SwiGLU FFN, every layer alike.

Stated from a configuration file's published keys (Hugging Face
``config.json`` names), independent of the program's own ``ModelConfig``.
The interface is described in ``chipbench/archs/__init__.py``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

BF16 = 2


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

    def matmul_params(self) -> int:
        """Weights of all layers that enter a matrix multiplication."""
        return self.num_hidden_layers * self.layer_matmul_params()

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


def stated(a: Arch) -> dict:
    """The program ``ModelConfig`` fields the file fixes."""
    return {"d_model": a.hidden_size, "d_ff": a.intermediate_size,
            "num_layers": a.num_hidden_layers,
            "n_heads": a.num_attention_heads,
            "n_kv_heads": a.num_key_value_heads, "head_dim": a.head_dim,
            "vocab_size": a.vocab_size, "rope_theta": a.rope_theta,
            "norm_eps": a.rms_norm_eps, "qkv_bias": a.qkv_bias,
            "qk_norm": a.qk_norm, "dtype": a.dtype, "tie_embeddings": True,
            "family": "dense", "ffn_kind": "gated", "act": "silu"}


# ------------------------------------------------------------- weights

def block_layout(arch: Arch, n: int, d_ff: int) -> dict:
    """One scanned group of ``n`` layers with FFN width ``d_ff``."""
    d, q, kv, hd = arch.hidden_size, arch.q_dim, arch.kv_dim, arch.head_dim
    dt = jnp.dtype(arch.dtype)

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, dt)

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
    return {"ln1": {"scale": s(n, d)}, "mixer": mixer,
            "ln2": {"scale": s(n, d)},
            "ffn": {"w_gate": dense(d, d_ff), "w_in": dense(d, d_ff),
                    "w_out": dense(d_ff, d)}}


def layout(arch: Arch, head: str) -> dict:
    """ShapeDtypeStruct tree of one model (``head`` is "lm" or "value")."""
    d = arch.hidden_size
    dt = jnp.dtype(arch.dtype)
    block = block_layout(arch, arch.num_hidden_layers, arch.intermediate_size)
    tree = {"embed": {"table": jax.ShapeDtypeStruct((arch.vocab_size, d), dt)},
            "groups": [{"b0": block}],
            "final_norm": {"scale": jax.ShapeDtypeStruct((d,), dt)}}
    if head == "value":
        tree["value_head"] = {"w": jax.ShapeDtypeStruct((d, 1), jnp.float32)}
    return tree


def fan_in(arch: Arch, path: str, shape) -> int:
    """Matrices at fan_in**-0.5; the tied table is also the LM head, so it
    is drawn at d_model**-0.5 and logits have unit scale."""
    return arch.hidden_size if "table" in path else shape[-2]


# ----------------------------------------------------------- reference

def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """Rotate-half RoPE over positions 0..S-1; x (B, S, H, D)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(p, arch: Arch, tokens, dot):
    """Final-norm hidden states (B, S, D) in float32; each entry of
    ``groups`` is scanned in turn."""
    b, s = tokens.shape
    h, hkv, hd = (arch.num_attention_heads, arch.num_key_value_heads,
                  arch.head_dim)
    eps = arch.rms_norm_eps
    causal = jnp.tril(jnp.ones((s, s), bool))
    x = p["embed"]["table"][tokens]

    def proj(y, w):
        out = dot("bsd,df->bsf", y, w["w"])
        return out + w["b"] if "b" in w else out

    def layer(x, lp):
        m = lp["mixer"]
        y = _rms(x, lp["ln1"]["scale"], eps)
        q = proj(y, m["wq"]).reshape(b, s, h, hd)
        k = proj(y, m["wk"]).reshape(b, s, hkv, hd)
        v = proj(y, m["wv"]).reshape(b, s, hkv, hd)
        if "q_norm" in m:
            q = _rms(q, m["q_norm"]["scale"], eps)
            k = _rms(k, m["k_norm"]["scale"], eps)
        q, k = _rope(q, arch.rope_theta), _rope(k, arch.rope_theta)
        q = q.reshape(b, s, hkv, h // hkv, hd)
        sc = dot("bqkgd,bskd->bkgqs", q, k) * hd ** -0.5
        sc = jnp.where(causal, sc, -jnp.inf)
        att = dot("bkgqs,bskd->bqkgd", jax.nn.softmax(sc, axis=-1), v)
        x = x + dot("bsq,qd->bsd", att.reshape(b, s, h * hd), m["wo"]["w"])
        y = _rms(x, lp["ln2"]["scale"], eps)
        f = lp["ffn"]
        g = jax.nn.silu(proj(y, f["w_gate"])) * proj(y, f["w_in"])
        return x + proj(g, f["w_out"]), None

    for group in p["groups"]:
        x, _ = jax.lax.scan(jax.checkpoint(layer), x, group["b0"])
    return _rms(x, p["final_norm"]["scale"], eps)


def lm_head(p):
    """The tied table."""
    return p["embed"]["table"]


# ------------------------------------------------------------ counting
# Model FLOPs: a matmul of an (m, k) by a (k, n) matrix is 2mkn; causal
# attention counts the (query, key) pairs a query attends to, 4 * head_dim
# FLOPs per pair and head (scores and the weighted sum); the LM head counts
# only the positions whose logprob is needed.  Recomputation (remat) and the
# program's other waste do not count.  Bytes are the least traffic to HBM:
# weights read once per pass, the KV cache read up to each decode step's
# length, and the optimizer's reads and writes in a train step.

def trunk_flops(a: Arch, tokens: int) -> int:
    """The layers' matmuls over ``tokens`` tokens, without attention."""
    return 2 * a.matmul_params() * tokens


def attention_flops(a: Arch, pairs: int) -> int:
    """Scores and weighted sum over ``pairs`` (query, key) pairs."""
    return 4 * a.num_attention_heads * a.head_dim * a.num_hidden_layers * pairs


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def head_flops(a: Arch, positions: int, head: str) -> int:
    width = a.vocab_size if head == "lm" else 1
    return 2 * a.hidden_size * width * positions


def forward_flops(a: Arch, batch: int, seq: int, head: str,
                  head_positions: int) -> int:
    """One forward over (batch, seq) with the head at ``head_positions``
    positions per row."""
    return (trunk_flops(a, batch * seq)
            + attention_flops(a, batch * causal_pairs(seq))
            + head_flops(a, batch * head_positions, head))


def weight_bytes(a: Arch, head: str = "lm") -> int:
    """bf16 weights (the value head's few fp32 entries counted as bf16)."""
    return a.param_count(head) * BF16


def kv_bytes_per_token(a: Arch) -> int:
    return 2 * a.num_hidden_layers * a.kv_dim * BF16


def generate(a: Arch, batch: int, prompt: int, gen: int) -> dict:
    """Prefill of the prompt (head at its last position), then ``gen - 1``
    decode steps: step i feeds the token at position prompt + i, which
    attends to prompt + i + 1 positions."""
    ctx = [prompt + i + 1 for i in range(gen - 1)]
    flops = (trunk_flops(a, batch * prompt)
             + attention_flops(a, batch * causal_pairs(prompt))
             + head_flops(a, batch, "lm")
             + (gen - 1) * (trunk_flops(a, batch) + head_flops(a, batch, "lm"))
             + attention_flops(a, batch * sum(ctx)))
    nbytes = (gen * weight_bytes(a)
              + batch * kv_bytes_per_token(a) * (prompt + sum(ctx)))
    return {"flops": flops, "bytes": nbytes}


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
        "ref_inf": {"flops": lm, "bytes": weight_bytes(a)},
        "reward_inf": {"flops": reward, "bytes": weight_bytes(a, "value")},
        "critic_inf": {"flops": val, "bytes": weight_bytes(a, "value")},
        "actor_train": {"flops": 3 * lm,
                        "bytes": train_bytes(a, "lm", minibatches)},
        "critic_train": {"flops": 3 * val,
                         "bytes": train_bytes(a, "value", minibatches)},
    }
