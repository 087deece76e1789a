"""The FLOP and byte counts against a hand count at a reduced size."""

import math

import jax

from chipbench import archs, flops

dense = archs.load("dense")
TINY = dense.Arch(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                  vocab_size=512, rope_theta=1e6, rms_norm_eps=1e-6,
                  qkv_bias=True, qk_norm=False)


def test_one_layer_matmul_flops():
    # q and o: 64x64, k and v: 64x32, gate/in: 64x128, out: 128x64
    per_token = 2 * (64 * 64 + 64 * 32 + 64 * 32 + 64 * 64 + 3 * 64 * 128)
    assert per_token == 73728
    assert dense.trunk_flops(TINY, 1) == 2 * per_token  # two layers
    assert dense.trunk_flops(TINY, 10) == 20 * per_token


def test_attention_and_head_flops():
    # 3 causal positions attend to 1 + 2 + 3 keys; 4 heads of 16, 2 layers
    assert dense.causal_pairs(3) == 6
    assert dense.attention_flops(TINY, 6) == 6 * 4 * 16 * 4 * 2
    assert dense.head_flops(TINY, 5, "lm") == 5 * 2 * 64 * 512
    assert dense.head_flops(TINY, 5, "value") == 5 * 2 * 64


def test_weight_bytes_per_decode_step():
    layer = 64 * 64 * 2 + 64 * 32 * 2 + 3 * 64 * 128  # matmul weights
    layer += 64 + 32 + 32 + 2 * 64  # q/k/v biases, two norms
    params = 2 * layer + 512 * 64 + 64  # tied table, final norm
    assert params == 107072
    assert dense.weight_bytes(TINY) == 2 * params
    counted = sum(math.prod(s.shape) for s in
                  jax.tree.leaves(dense.layout(TINY, "lm")))
    assert counted == params


def test_generate_counts_each_decode_step():
    b, p, g = 2, 3, 4
    one = dense.generate(TINY, b, p, 1)  # prefill only
    assert one["flops"] == (dense.trunk_flops(TINY, b * p)
                            + dense.attention_flops(TINY, b * 6)
                            + dense.head_flops(TINY, b, "lm"))
    full = dense.generate(TINY, b, p, g)
    # decode steps feed positions 3, 4, 5 and attend to 4, 5, 6 keys
    decode = 3 * (dense.trunk_flops(TINY, b) + dense.head_flops(TINY, b, "lm"))
    assert full["flops"] == one["flops"] + decode + \
        dense.attention_flops(TINY, b * (4 + 5 + 6))
    kv = 2 * 2 * 2 * 16 * 2  # k and v, 2 layers, 2 heads of 16, bf16
    assert dense.kv_bytes_per_token(TINY) == kv
    assert full["bytes"] == 4 * dense.weight_bytes(TINY) + b * kv * (3 + 15)


def test_roofline_names_its_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_seconds({"flops": 500, "bytes": 20},
                               peak) == (5.0, "flops")
    assert flops.least_seconds({"flops": 100, "bytes": 50},
                               peak) == (5.0, "bytes")
