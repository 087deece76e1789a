"""Batched serving example: prefill a batch of prompts once, then stream
decode steps from the compiled cache loop — the serving-side substrate the
actor-generation function call uses.

    PYTHONPATH=src python examples/serve_batch.py [--batch 4] [--new 24]
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCHS
from repro.models import decode_step, generate, init_params, prefill, synth_batch
from repro.models.model import logits_of


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new", type=int, default=24)
    args = ap.parse_args()

    cfg = ARCHS[args.arch].reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = synth_batch(jax.random.PRNGKey(1), cfg, args.prompt_len,
                        args.batch, "prefill")

    # one compiled generate = prefill + scanned decode (no per-token dispatch,
    # the TPU analogue of the paper's CUDAGraph decode)
    gen = jax.jit(lambda p, b, k: generate(
        p, cfg, b, num_new_tokens=args.new, rng=k))
    t0 = time.time()
    out = gen(params, batch, jax.random.PRNGKey(2))
    jax.block_until_ready(out["tokens"])
    compile_s = time.time() - t0
    t0 = time.time()
    out = gen(params, batch, jax.random.PRNGKey(3))
    jax.block_until_ready(out["tokens"])
    run_s = time.time() - t0

    toks = args.batch * args.new
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"new={args.new}")
    print(f"compile {compile_s:.1f}s; steady-state {run_s*1e3:.0f}ms "
          f"=> {toks/run_s:,.0f} tok/s on CPU")
    print("sample token ids:", out["tokens"][0][:10].tolist())
    print("mean logprob:", float(out["logprobs"].mean()))

    # interactive-style serving: explicit prefill + stepwise decode
    last_h, caches, _ = prefill(params, cfg, batch,
                             max_len=args.prompt_len + args.new)
    lg = logits_of(params, cfg, last_h[:, None])[:, 0]
    tok = jnp.argmax(lg, -1).astype(jnp.int32)
    for t in range(args.prompt_len, args.prompt_len + 4):
        lg, caches, _ = decode_step(params, cfg, tok, caches, jnp.int32(t))
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
    print("stepwise decode OK; final greedy ids:", tok.tolist())


if __name__ == "__main__":
    main()
