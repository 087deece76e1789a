"""Operations and bytes that each call of a PPO iteration needs, from the
configuration and the traffic.

Model FLOPs: a matmul of an (m, k) by a (k, n) matrix is 2mkn; causal
attention counts the (query, key) pairs a query attends to, 4 * head_dim
FLOPs per pair and head (scores and the weighted sum); the LM head counts
only the positions whose logprob is needed.  Recomputation (remat) and the
program's other waste do not count.  Bytes are the least traffic to HBM:
weights read once per pass, the KV cache read up to each decode step's
length, and the optimizer's reads and writes in a train step.
"""

from __future__ import annotations

from chipbench.arch import Arch

BF16 = 2


def trunk_flops(a: Arch, tokens: int) -> int:
    """The layers' matmuls over ``tokens`` tokens, without attention."""
    return 2 * a.layer_matmul_params() * a.num_hidden_layers * tokens


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


def least_seconds(cost: dict, peak: dict) -> tuple[float, str]:
    """The roofline: the larger of operations over peak FLOP/s and bytes
    over peak bytes/s, and which of the two bounds it."""
    t_f = cost["flops"] / peak["flops_per_s"]
    t_b = cost["bytes"] / peak["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
