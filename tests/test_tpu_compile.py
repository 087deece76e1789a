"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

Interpret mode does not check the chip's (8, 128) block tiling, SMEM/VMEM
placement or Mosaic's lowering rules; these tests run the TPU compiler
against a described (not attached) ``v5e:2x2`` topology at qwen2-0.5b
widths (14 query heads, 2 KV heads, head_dim 64, bf16), and at
DeepSeek-V2-Lite's for MLA and the grouped expert GEMM.  Nothing runs, so
they say nothing about results or speed -- except what the compiled program
holds: the decode loop of ``generate`` is read for ops that move the KV
cache (the latent cache, for MLA).

The topology is described inside a fixture, never at import, and the
persistent compilation cache is off around the compiles (an entry written
for a described chip cannot be read back without one).
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, HQ, HKV, D = 8, 14, 2, 64  # qwen2-0.5b attention widths
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("seq,positions", [(256, False), (256, True),
                                           (5, True)])
def test_flash_mha_compiles(one_chip, seq, positions):
    """Prefill / train widths (seq 256), and the verify-step shape (5
    queries against a 256-key cache with explicit positions)."""
    from repro.kernels.flash_attention import flash_mha
    skv = 256

    def fn(q, k, v, qp, kp):
        if not positions:
            qp = kp = None
        return flash_mha(q, k, v, causal=True, q_positions=qp,
                         kv_positions=kp)

    _compile(fn, one_chip, ((B, seq, HQ, D), BF16), ((B, skv, HKV, D), BF16),
             ((B, skv, HKV, D), BF16), ((B, seq), jnp.int32),
             ((B, skv), jnp.int32))


def test_flash_decode_compiles(one_chip):
    from repro.kernels.decode_attention import flash_decode
    cap = 256
    _compile(lambda q, k, v, n: flash_decode(q, k, v, cache_len=n), one_chip,
             ((B, HQ, D), BF16), ((B, cap, HKV, D), BF16),
             ((B, cap, HKV, D), BF16), ((B,), jnp.int32))


def test_paged_flash_decode_compiles(one_chip):
    from repro.kernels.paged_decode_attention import paged_flash_decode
    bs, m = 16, 16
    n = B * m + 1
    _compile(lambda q, k, v, t, c: paged_flash_decode(q, k, v, t, cache_len=c),
             one_chip, ((B, HQ, D), BF16), ((n, bs, HKV, D), BF16),
             ((n, bs, HKV, D), BF16), ((B, m), jnp.int32), ((B,), jnp.int32))


def test_flash_mha_mla_widths_compile(one_chip):
    """DeepSeek-V2's expanded MLA prefill: q/k of 192 and v of 128 (zero
    columns to 192 inside ``ops.mha``) at YaRN's softmax scale, 16 heads."""
    from repro.kernels import ops
    s, h = 256, 16
    _compile(lambda q, k, v: ops.mha(q, k, v, causal=True, scale=0.1147,
                                     impl="pallas"),
             one_chip, ((B, s, h, 192), BF16), ((B, s, h, 192), BF16),
             ((B, s, h, 128), BF16))


@pytest.mark.parametrize("rows", [192, 6144])
def test_grouped_expert_compiles(one_chip, rows):
    """The grouped expert GEMM at DeepSeek-V2-Lite's widths (D 2048, F 1408)
    over 8 held experts: a decode step's cohort (32 tokens x top 6) and a
    dispatch chunk's (1024 tokens x top 6)."""
    from repro.kernels.grouped_expert import grouped_ffn
    e, d, f = 8, 2048, 1408
    compiled = _compile(
        lambda x, g, wg, wi, wo: grouped_ffn(x, g, wg, wi, wo), one_chip,
        ((rows, d), BF16), ((e,), jnp.int32), ((e, d, f), BF16),
        ((e, d, f), BF16), ((e, f, d), BF16))
    assert "grouped_ffn" in compiled.as_text()


def test_flash_mha_varlen_compiles(one_chip):
    from repro.kernels.varlen_attention import flash_mha_varlen
    t = 2048
    _compile(lambda q, k, v, cu: flash_mha_varlen(q, k, v, cu), one_chip,
             ((t, HQ, D), BF16), ((t, HKV, D), BF16), ((t, HKV, D), BF16),
             ((B + 1,), jnp.int32))


# ------------------------------------------------ the compiled decode loop

_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1}
# ops that yield a new buffer from an existing one
_MOVES = ("copy", "copy-start", "pad", "dynamic-slice", "slice-start",
          "transpose")


def _computations(hlo):
    """{computation: {instruction: (type, opcode, rest of line)}}."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), {})
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            m = re.match(
                r"^\s*(?:ROOT )?%(\S+) = (.+?) ([a-z][a-z0-9-]*)\((.*)$", line)
            if m:
                cur[m.group(1)] = m.group(2, 3, 4)
    return comps


def _arrays(typ):
    """[(bytes, dims)] of each array in an HLO type."""
    out = []
    typ = re.sub(r"\{[^}]*\}", "", typ)  # layouts
    for dt, dims in re.findall(r"(\w+)\[([\d,]*)\]", typ):
        dims = tuple(int(x) for x in dims.split(",") if x)
        n = _BYTES.get(dt, 4)
        for x in dims:
            n *= x
        out.append((n, dims))
    return out


def _called(rest, key="calls|body|condition|to_apply|called_computations"):
    return re.findall(rf"(?:{key})=\{{?%([\w.\-]+)", rest)


def _decode_loop_bodies(comps):
    """Bodies of the while loops that reach ``flash_decode``, with the
    computations their fusions call."""
    def reach(c, seen):
        if c not in seen and c in comps:
            seen.add(c)
            for _, _, rest in comps[c].values():
                for cc in _called(rest):
                    reach(cc, seen)
        return seen
    bodies = set()
    for ins in comps.values():
        for _, op, rest in ins.values():
            if op == "while":
                body = _called(rest, "body")[0]
                inner = reach(body, set())
                if any(n.startswith("flash_decode") for c in inner
                       for n in comps[c]):
                    bodies |= inner
    return bodies


@pytest.mark.parametrize("arch,layers,prompt,gen", [
    ("qwen2-0.5b", 3, 128, 512),   # (2, 64) heads, capacity 640 = 5 x 128
    ("qwen3-1.7b", 2, 128, 1024),  # (8, 128) heads, qk-norm, capacity 1152
])
def test_decode_loop_moves_no_cache(one_chip, arch, layers, prompt, gen):
    """``generate(fused=True, impl="pallas")`` at the benchmark's attention
    widths and batch: inside the decode loop no copy, pad or slice yields a
    buffer of one layer's KV cache or more, and every update of a cache
    writes one token.  A capacity that 256 does not divide would catch a
    pad to the kernel's tile."""
    from repro.configs import ARCHS
    from repro.models import model as MDL
    cfg = dataclasses.replace(ARCHS[arch], num_layers=layers,
                              n_superblocks=layers, vocab_size=4096)
    cap = prompt + gen
    layer_bytes = B * cap * cfg.kv_dim * 2
    token_bytes = B * cfg.kv_dim * 2
    on_chip = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                             sharding=one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda k: MDL.init_params(k, cfg), jax.random.PRNGKey(0)))
    batch = {"tokens": on_chip(jax.ShapeDtypeStruct((B, prompt), jnp.int32))}
    key = on_chip(jax.ShapeDtypeStruct((2,), jnp.uint32))
    hlo = jax.jit(lambda p, b, k: MDL.generate(
        p, cfg, b, num_new_tokens=gen, rng=k, impl="pallas", fused=True)
    ).lower(params, batch, key).compile().as_text()

    comps = _computations(hlo)
    bodies = _decode_loop_bodies(comps)
    assert bodies, "no while loop reaches flash_decode"
    moves, updates = [], []
    for c in bodies:
        ins = comps[c]
        for name, (typ, op, rest) in ins.items():
            operands = [ins[o][0] for o in re.findall(r"%([\w.\-]+)",
                                                       rest.split("),")[0])
                        if o in ins]
            shapes = [a for t in [typ] + operands for a in _arrays(t)]
            touches_cache = any(cap in dims for _, dims in shapes)
            if op in _MOVES and touches_cache and max(
                    n for n, _ in _arrays(typ)) >= layer_bytes:
                moves.append(f"{name} {op} {typ[:80]}")
            if op == "dynamic-update-slice" and touches_cache:
                update = sum(n for n, _ in _arrays(operands[1]))
                if update > token_bytes:
                    updates.append(f"{name}: {update} bytes")
    assert not moves, moves
    assert not updates, updates


def test_mla_decode_loop_moves_no_latent_cache_in_hbm(one_chip):
    """``generate(fused=True, impl="pallas")`` of DeepSeek-V2-Lite's share
    (a dense layer and two MoE layers holding 8 experts, batch 32, prompt
    128, 512 generated): inside the decode loop no copy, pad or slice
    writes a buffer of one layer's latent cache or more to HBM, and every
    update of a cache writes one token.  XLA stages each layer's latents in
    VMEM for the absorbed attention (a dynamic slice and a relayout there,
    memory space ``S(1)``): that reads the layer once from HBM and writes
    nothing back; a latent-cache decode kernel would read it in place."""
    from repro.configs import ARCHS
    from repro.models import model as MDL
    cfg = dataclasses.replace(ARCHS["deepseek-v2-lite"], num_layers=3,
                              n_superblocks=2, vocab_size=4096,
                              n_local_experts=8)
    b, prompt, gen = 32, 128, 512
    cap, width = prompt + gen, cfg.kv_lora_rank + cfg.qk_rope_head_dim
    layer_bytes, token_bytes = b * cap * width * 2, b * width * 2
    on_chip = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                             sharding=one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda k: MDL.init_params(k, cfg), jax.random.PRNGKey(0)))
    batch = {"tokens": on_chip(jax.ShapeDtypeStruct((b, prompt), jnp.int32))}
    key = on_chip(jax.ShapeDtypeStruct((2,), jnp.uint32))
    hlo = jax.jit(lambda p, bt, k: MDL.generate(
        p, cfg, bt, num_new_tokens=gen, rng=k, impl="pallas", fused=True)
    ).lower(params, batch, key).compile().as_text()

    comps = _computations(hlo)

    def reach(c, seen):
        if c not in seen and c in comps:
            seen.add(c)
            for _, _, rest in comps[c].values():
                for cc in _called(rest):
                    reach(cc, seen)
        return seen
    # the decode loop carries the caches and the (gen - 1, B) sampled tokens
    loops = [reach(_called(rest, "body")[0], set())
             for ins in comps.values() for typ, op, rest in ins.values()
             if op == "while" and f"{cap},{width}]" in typ
             and f"[{gen - 1},{b}]" in typ]
    assert len(loops) == 1, "no single decode loop"
    bodies = loops[0]
    assert any(n.startswith("grouped_ffn") for c in bodies for n in comps[c])
    moves, updates = [], []
    for c in bodies:
        ins = comps[c]
        for name, (typ, op, rest) in ins.items():
            operands = [ins[o][0] for o in re.findall(r"%([\w.\-]+)",
                                                       rest.split("),")[0])
                        if o in ins]
            shapes = [a for t in [typ] + operands for a in _arrays(t)]
            touches_cache = any(cap in dims and width in dims
                                for _, dims in shapes)
            in_hbm = "S(1)" not in typ
            if op in _MOVES and touches_cache and in_hbm and max(
                    n for n, _ in _arrays(typ)) >= layer_bytes:
                moves.append(f"{name} {op} {typ[:80]}")
            if op == "dynamic-update-slice" and touches_cache:
                update = sum(n for n, _ in _arrays(operands[1]))
                if update > token_bytes:
                    updates.append(f"{name}: {update} bytes")
    assert not moves, moves
    assert not updates, updates
