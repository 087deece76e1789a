"""MoE dispatch benchmark: dropless grouped dispatch vs capacity-drop decode.

Times the single-token decode loop of a reduced MoE config under both
``moe_dispatch`` modes and accounts the dispatch-buffer padding each mode
pays per step.  The capacity path always materializes ``E x capacity``
expert rows — with the ``max(8, ...)`` floor, a small decode cohort pads a
handful of real rows up to ``E x 8`` — while the dropless grouped dispatch
runs exactly ``B x top_k`` rows (zero padded expert rows) *and* is the mode
whose decode bit-matches the training forward (see ``tests/test_moe.py``).

    PYTHONPATH=src python -m benchmarks.moe_bench --smoke --json out.json

Wired into ``benchmarks/run.py`` as ``--only moe``; CI runs ``--smoke`` and
uploads the JSON artifact alongside the serve/rollout benches.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time


def bench_moe(arch="granite-moe-1b-a400m", batch=8, n_experts=16, top_k=2,
              prompt=16, steps=64, reps=3):
    import jax
    import jax.numpy as jnp
    from repro.configs import ARCHS
    from repro.models import decode_step, init_params, prefill, synth_batch
    from repro.models.moe import capacity

    base = dataclasses.replace(ARCHS[arch].reduced(), n_experts=n_experts,
                               top_k=top_k)
    key = jax.random.PRNGKey(0)
    real_rows = batch * top_k  # rows a decode step actually routes
    modes = {}
    for mode in ("dropless", "capacity"):
        cfg = dataclasses.replace(base, moe_dispatch=mode)
        params = init_params(key, cfg)
        pb = synth_batch(jax.random.PRNGKey(1), cfg, prompt, batch, "prefill")
        last_h, caches, _ = jax.jit(
            lambda p, b: prefill(p, cfg, b, max_len=prompt + steps))(params, pb)

        def decode_n(p, tok, caches, cfg=cfg):
            def body(carry, t):
                tok, caches = carry
                lg, caches, _ = decode_step(p, cfg, tok, caches, t)
                return (jnp.argmax(lg, -1).astype(jnp.int32), caches), None
            (tok, _), _ = jax.lax.scan(
                body, (tok, caches), prompt + jnp.arange(steps, dtype=jnp.int32))
            return tok

        fn = jax.jit(decode_n)
        tok0 = pb["tokens"][:, -1]
        fn(params, tok0, caches).block_until_ready()  # compile
        best = min(_timed(fn, params, tok0, caches) for _ in range(reps))

        if mode == "capacity":
            dispatch_rows = cfg.n_experts * capacity(batch, cfg)
        else:
            dispatch_rows = real_rows
        modes[mode] = {
            "tok_s": batch * steps / best,
            "wall_s": best,
            "dispatch_rows_per_step": dispatch_rows,
            "padded_rows_per_step": dispatch_rows - real_rows,
        }

    speedup = modes["dropless"]["tok_s"] / modes["capacity"]["tok_s"]
    summary = {
        "model": base.name, "batch": batch, "decode_steps": steps,
        "n_experts": n_experts, "top_k": top_k,
        "real_rows_per_step": real_rows,
        "dropless": modes["dropless"], "capacity": modes["capacity"],
        "speedup": speedup,
    }
    rows = [
        (f"moe/{m}", modes[m]["wall_s"] / (batch * steps) * 1e6,
         f"tok_s={modes[m]['tok_s']:.0f};"
         f"padded_rows={modes[m]['padded_rows_per_step']}")
        for m in ("dropless", "capacity")
    ] + [("moe/speedup", 0.0, f"dropless_over_capacity={speedup:.2f}x")]
    return rows, summary


def _timed(fn, *args):
    t0 = time.perf_counter()
    fn(*args).block_until_ready()
    return time.perf_counter() - t0


def run():
    return bench_moe()[0]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small CI-friendly workload")
    ap.add_argument("--json", default=None,
                    help="write the summary dict to this path")
    args = ap.parse_args()

    from benchmarks.common import emit
    kw = dict(batch=4, steps=24, reps=2) if args.smoke else {}
    rows, summary = bench_moe(**kw)
    emit(rows)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=2)


if __name__ == "__main__":
    main()
