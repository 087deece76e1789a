#!/usr/bin/env python3
"""Smoke run of the RLHF main path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --four-chip  # four chips: the sharded phase only

One chip: the attention kernels the rollout uses (``flash_mha``,
``flash_decode``, ``paged_flash_decode``) against the ``reference`` tier at
qwen2-0.5b widths, then three PPO iterations of full-width qwen2-0.5b
(actor = critic config, ref/reward copies) through ``launch/train.py``'s
``build_experiment`` / ``train`` with ``rollout_impl="pallas"``.

``--four-chip``: the full-width actor train step TP/FSDP-sharded on a
(2, 2) ("data", "model") mesh against the same step on one chip, and a
round trip of the actor's params and optimizer state through
``realloc_exec`` from that train layout to a (1, 4) TP generation layout.

Every phase asserts its checks; the last line of standard output is one
JSON object naming the device.  Without a TPU the script exits non-zero
before running anything and prints no result line.  One process drives
every chip; nothing here starts a child.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "qwen2-0.5b"
# rollout + training sizes the compiled programs show fit one 16 GiB v5e
# with bf16 AdamW moments (see CHANGES.md)
PPO_ARGS = ["--arch", ARCH, "--batch", "8", "--prompt-len", "128",
            "--gen-len", "128", "--rollout-impl", "pallas",
            "--opt-state-dtype", "bfloat16", "--search-iters", "50"]
ITERATIONS = 3
KERNEL_TOL = 3e-2  # max abs error of a bf16-output kernel vs fp32 reference
# mean |rollout - ref logprob| per generated token at iteration 0, where
# actor == ref: the Pallas rollout against the reference-tier forward of
# the same weights.  At qwen2-0.5b width on the CPU (depth cut to 2/6/12)
# it reads 6.9e-03/7.7e-03/8.4e-03 sound and 5.9e-02/4.4e-02/3.5e-02 with
# flash_decode's cache_len off by one (PERF.md, PR 11)
GAP_TOL = 2e-2


def require_tpu(count: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {len(devs)} "
                 f"{devs[0].platform!r} device(s).  Nothing was run.")
    if len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} TPU chips; JAX found "
                 f"{len(devs)}.  Nothing was run.")
    return devs


def _check(name, err, tol):
    print(f"parity {name}: max_abs_err={err:.3e} bound={tol:.1e}",
          flush=True)
    assert math.isfinite(err) and err <= tol, (name, err, tol)


def kernel_parity(cfg, batch=8, seq=256):
    """Pallas tier vs the fp32 reference tier at the model's head widths,
    on the device."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (batch, seq, hq, d), bf)
    k = jax.random.normal(ks[1], (batch, seq, hkv, d), bf)
    v = jax.random.normal(ks[2], (batch, seq, hkv, d), bf)
    f32 = lambda *xs: [x.astype(jnp.float32) for x in xs]  # noqa: E731

    def err(kernel, reference, *args):
        out = jax.jit(kernel)(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(reference)(*f32(*args))
        return float(jnp.max(jnp.abs(out.astype(jnp.float32) - want)))

    # prefill: causal over arange positions
    _check("flash_mha", err(
        lambda q, k, v: ops.mha(q, k, v, impl="pallas"),
        lambda q, k, v: ops.mha(q, k, v, impl="reference"), q, k, v),
        KERNEL_TOL)
    # speculative-verify shape: 5 queries at offset positions
    pos = 20 + jnp.arange(5)[None] + 29 * jnp.arange(batch)[:, None]
    kpos = jnp.arange(seq)[None]
    _check("flash_mha@positions", err(
        lambda q, k, v: ops.mha(q, k, v, q_positions=pos, kv_positions=kpos,
                                impl="pallas"),
        lambda q, k, v: ops.mha(q, k, v, q_positions=pos, kv_positions=kpos,
                                impl="reference"), q[:, :5], k, v),
        KERNEL_TOL)
    lens = jnp.arange(1, batch + 1, dtype=jnp.int32) * (seq // batch) - 3
    _check("flash_decode", err(
        lambda q, k, v: ops.decode_mha(q, k, v, cache_len=lens, impl="pallas"),
        lambda q, k, v: ops.decode_mha(q, k, v, cache_len=lens,
                                       impl="reference"), q[:, 0], k, v),
        KERNEL_TOL)
    bs = 16
    m = seq // bs
    pool_k = k.reshape(batch * m, bs, hkv, d)
    pool_v = v.reshape(batch * m, bs, hkv, d)
    tbl = jax.random.permutation(ks[3], batch * m).reshape(batch, m)
    _check("paged_flash_decode", err(
        lambda q, k, v: ops.paged_decode_mha(q, k, v, tbl, cache_len=lens,
                                             impl="pallas"),
        lambda q, k, v: ops.paged_decode_mha(q, k, v, tbl, cache_len=lens,
                                             impl="reference"),
        q[:, 0], pool_k, pool_v), KERNEL_TOL)


def ppo_phase(cfg):
    from repro.launch import train as T

    args = T.parser().parse_args(PPO_ARGS)
    t0 = time.perf_counter()
    run = T.build_experiment(args)
    print(f"build (init + plan search): {time.perf_counter() - t0:.3f}s",
          flush=True)
    records = T.train(run, ITERATIONS)
    for r in records:
        label = "compile" if r["step"] == 0 else "warm"
        print(f"iteration {r['step']} [{label}]: {r['seconds']:.3f}s "
              f"actor_loss={r['actor_loss']:.6g} "
              f"critic_loss={r['critic_loss']:.6g} "
              f"reward_mean={r['reward_mean']:.6g} kl={r['kl']:.6g} "
              f"logp_gap={r['logp_gap']:.6g} logp_mean={r['logp_mean']:.6g}",
              flush=True)
        for key in ("actor_loss", "critic_loss", "reward_mean", "kl",
                    "logp_gap", "logp_mean"):
            assert math.isfinite(r[key]), (r["step"], key, r[key])
    assert len(records) == ITERATIONS
    # a rollout whose logprobs sit at 0 would make the gap check empty
    logp_max = -0.5 * math.log(cfg.vocab_size)
    r0 = records[0]
    print(f"iteration 0: logp_gap={r0['logp_gap']:.3e} bound={GAP_TOL:.1e} "
          f"logp_mean={r0['logp_mean']:.4f} bound={logp_max:.4f}", flush=True)
    assert r0["logp_gap"] <= GAP_TOL, r0
    assert r0["logp_mean"] <= logp_max, r0


def _tree_bytes_per_device(tree):
    import jax
    per = {}
    for leaf in jax.tree.leaves(tree):
        for s in leaf.addressable_shards:
            per[s.device.id] = per.get(s.device.id, 0) + s.data.nbytes
    return per


def _check_placement(name, tree, devices, total):
    """Every device holds its own part: no device holds the whole tree,
    and every TP/FSDP-sharded leaf spans all devices."""
    import jax
    per = _tree_bytes_per_device(tree)
    print(f"{name}: bytes per device "
          f"{ {k: per[k] for k in sorted(per)} } of {total} total",
          flush=True)
    assert sorted(per) == sorted(d.id for d in devices), per
    assert max(per.values()) < 0.5 * total, per
    for leaf in jax.tree.leaves(tree):
        if not leaf.sharding.is_fully_replicated:
            assert {s.device.id for s in leaf.addressable_shards} == set(per)


def four_chip_phase(cfg, devices, *, batch=8, seq=256, lr=1e-5):
    """Sharded actor train step vs one chip, then a train -> generation ->
    train layout round trip of params + optimizer state."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding
    from repro.models import model as MDL
    from repro.optim import adamw
    from repro.parallel import sharding as SH
    from repro.parallel.realloc_exec import prefetch_reshard
    from repro.rlhf import ppo as PPO

    gen_start = seq // 2
    g = seq - gen_start
    opt_cfg = adamw.AdamWConfig(lr=lr, state_dtype="bfloat16")
    hp = PPO.PPOHyperparameters(n_minibatches=2)
    step = PPO.make_actor_train_step(cfg, hp, opt_cfg, gen_start)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    one = SingleDeviceSharding(devices[0])
    params = jax.jit(lambda: MDL.init_params(ks[0], cfg, head="lm"),
                     out_shardings=one)()
    opt = jax.jit(lambda p: adamw.init(opt_cfg, p), out_shardings=one)(params)
    tokens = jax.random.randint(ks[1], (batch, seq), 0, cfg.vocab_size,
                                jnp.int32)
    # old logprobs = the current policy's, so the first PPO ratio is 1 and
    # no token is clipped out of the gradient
    logp = jax.jit(lambda p, t: PPO.sequence_logprobs(p, cfg, t, gen_start))(
        params, tokens)
    data = {"tokens": tokens, "logp": logp,
            "adv": jax.random.normal(ks[2], (batch, g)),
            "mask": jnp.ones((batch, g))}

    # one chip
    t0 = time.perf_counter()
    p1, o1, s1 = jax.jit(step)(params, opt, data)
    s1 = jax.tree.map(float, s1)
    moment1 = jax.device_get(o1["m"])
    print(f"one-chip step (compile + run): {time.perf_counter() - t0:.3f}s "
          f"loss={s1['loss']:.6g} grad_norm={s1['grad_norm']:.6g}",
          flush=True)
    del p1, o1

    # (2, 2) ("data", "model"): FSDP over data, TP over model
    auto = (AxisType.Auto,) * 2
    mesh = jax.make_mesh((2, 2), ("data", "model"), axis_types=auto,
                         devices=devices)
    rules = SH.ShardingRules()

    def shardings(m):
        spec = SH.sanitize_specs(SH.param_specs(params, rules), params, m)
        ns = lambda t: jax.tree.map(  # noqa: E731
            lambda s: NamedSharding(m, s), t,
            is_leaf=lambda x: isinstance(x, P))
        return ns(spec), ns(SH.opt_state_specs(spec, rules))

    gen_mesh = jax.make_mesh((1, 4), ("data", "model"), axis_types=auto,
                             devices=devices)
    psh, osh = shardings(mesh)
    gp, go = shardings(gen_mesh)
    bsh = jax.tree.map(lambda x: NamedSharding(
        mesh, P("data", *([None] * (x.ndim - 1)))), data)
    t0 = time.perf_counter()
    p2, o2, s2 = jax.jit(step, in_shardings=(psh, osh, bsh),
                         out_shardings=(psh, osh, None))(
        jax.device_put(params, psh), jax.device_put(opt, osh),
        jax.device_put(data, bsh))
    s2 = jax.tree.map(float, s2)
    print(f"(2,2) step (compile + run): {time.perf_counter() - t0:.3f}s "
          f"loss={s2['loss']:.6g} grad_norm={s2['grad_norm']:.6g}",
          flush=True)
    del params, opt
    # AdamW's first moment after the step is a fixed mix of the two
    # minibatch gradients, so it compares the gradients leaf by leaf
    loss_tol = 2e-3 + 1e-2 * abs(s1["loss"])
    gn_tol = 2e-2 * s1["grad_norm"]
    m_tol = 0.1  # bf16 gradients: reduction order alone gives ~3e-2

    def rel(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.linalg.norm(a - b) / max(np.linalg.norm(a), 1e-30))

    d_m, worst = max(
        (rel(a, b), jax.tree_util.keystr(path)) for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(moment1),
            jax.tree.leaves(jax.device_get(o2["m"]))))
    print(f"sharded vs one chip: |d loss|={abs(s1['loss'] - s2['loss']):.3e} "
          f"(bound {loss_tol:.1e}) |d grad_norm|="
          f"{abs(s1['grad_norm'] - s2['grad_norm']):.3e} "
          f"(bound {gn_tol:.1e}) worst leaf relative L2 of AdamW m="
          f"{d_m:.3e} at {worst} (bound {m_tol:.0e})", flush=True)
    assert abs(s1["loss"] - s2["loss"]) <= loss_tol
    assert abs(s1["grad_norm"] - s2["grad_norm"]) <= gn_tol
    assert d_m <= m_tol
    del moment1

    # reallocation: train layout -> (1, 4) TP generation layout -> back
    tree = {"params": p2, "opt": o2}
    host = jax.device_get(tree)
    _check_placement("train layout (2,2)", tree, devices,
                     sum(x.nbytes for x in jax.tree.leaves(host)))

    def bit_exact(t):
        return all(np.asarray(a).tobytes() == np.asarray(b).tobytes()
                   for a, b in zip(jax.tree.leaves(jax.device_get(t)),
                                   jax.tree.leaves(host)))

    for name, dst in (("train -> gen (1,4)", {"params": gp, "opt": go}),
                      ("gen -> train (2,2)", {"params": psh, "opt": osh})):
        t0 = time.perf_counter()
        task = prefetch_reshard(tree, dst)
        tree = task.wait()
        print(f"reshard {name}: moved {task.moved_bytes} of "
              f"{task.total_bytes} bytes ({task.n_moved} leaves moved, "
              f"{task.n_aliased} aliased) in "
              f"{time.perf_counter() - t0:.3f}s", flush=True)
        assert task.moved_bytes > 0
        _check_placement(name, tree, devices, task.total_bytes)
        assert bit_exact(tree), f"{name}: values changed"
        print(f"reshard {name}: bit-exact", flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-chip sharded phase")
    args = ap.parse_args(argv)
    count = 4 if args.four_chip else 1
    devs = require_tpu(count)
    from repro.configs import ARCHS

    cache = enable_compile_cache()
    print(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)} compile_cache={cache}", flush=True)
    cfg = ARCHS[ARCH]
    if args.four_chip:
        four_chip_phase(cfg, devs[:4])
    else:
        kernel_parity(cfg)
        ppo_phase(cfg)
    stats = devs[0].memory_stats() or {}
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"bytes_limit={stats.get('bytes_limit')}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": count}}))


if __name__ == "__main__":
    main()
