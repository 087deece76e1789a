"""Multi-device behaviours that need >1 XLA device: run in subprocesses with
their own XLA_FLAGS (the main test process keeps the 1-device view)."""

import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_with_devices(code: str, n: int = 8, timeout=420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_reshard_preserves_values_across_shardings():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.parallel.realloc_exec import reshard

        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        x = jnp.arange(64 * 32, dtype=jnp.float32).reshape(64, 32)
        a = jax.device_put(x, NamedSharding(mesh, P("data", "model")))
        tree = {"w": a, "b": jax.device_put(x[:, 0], NamedSharding(mesh, P("data")))}
        dst = {"w": NamedSharding(mesh, P("model", None)),
               "b": NamedSharding(mesh, P(None))}
        out = reshard(tree, dst)
        np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(x))
        np.testing.assert_array_equal(np.asarray(out["b"]), np.asarray(x[:, 0]))
        assert out["w"].sharding.spec == P("model", None)
        print("RESHARD_OK")
    """)
    assert "RESHARD_OK" in out


def test_tp_sharded_train_step_matches_single_device():
    """The same train step on a (2,2) mesh and on 1 device agree."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import ARCHS
        from repro.models import init_params, lm_loss, synth_batch
        from repro.optim import adamw
        from repro.parallel import sharding as SH
        from repro.parallel.steps import make_train_step

        cfg = ARCHS["qwen2-0.5b"].reduced()
        p = init_params(jax.random.PRNGKey(0), cfg)
        opt_cfg = adamw.AdamWConfig(lr=1e-3)
        opt = adamw.init(opt_cfg, p)
        batch = synth_batch(jax.random.PRNGKey(1), cfg, 16, 4, "train")
        step = make_train_step(cfg, opt_cfg)

        # single device
        p1, o1, m1 = jax.jit(step)(p, opt, batch)

        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        rules = SH.ShardingRules()
        psh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           SH.param_specs(p, rules))
        osh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           SH.opt_state_specs(SH.param_specs(p, rules), rules))
        bsh = jax.tree.map(
            lambda x: NamedSharding(mesh, P("data", *([None]*(x.ndim-1)))),
            batch)
        ps = jax.device_put(p, psh)
        os_ = jax.device_put(opt, osh)
        bs = jax.device_put(batch, bsh)
        p2, o2, m2 = jax.jit(step, in_shardings=(psh, osh, bsh))(ps, os_, bs)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3, (m1, m2)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=5e-3, rtol=1e-2)
        print("TRAIN_SHARD_OK")
    """, n=4)
    assert "TRAIN_SHARD_OK" in out


def test_pipeline_parallel_matches_sequential():
    out = run_with_devices("""
        import jax, jax.numpy as jnp
        from repro.parallel.pipeline import pipeline_apply, microbatch
        from jax.sharding import AxisType
        mesh = jax.make_mesh((4,), ("stage",),
                             axis_types=(AxisType.Auto,))
        rng = jax.random.PRNGKey(0)
        L, D, B, MBS = 8, 16, 12, 6
        ws = jax.random.normal(rng, (L, D, D)) * 0.3
        def layer_fn(w_stack, x):
            def body(x, w):
                return jnp.tanh(x @ w), None
            return jax.lax.scan(body, x, w_stack)[0]
        x = jax.random.normal(jax.random.PRNGKey(1), (B, D))
        ref = x
        for i in range(L):
            ref = jnp.tanh(ref @ ws[i])
        out = pipeline_apply(layer_fn, ws.reshape(4, 2, D, D),
                             microbatch(x, MBS), mesh=mesh).reshape(B, D)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-6
        print("PIPELINE_OK")
    """, n=4)
    assert "PIPELINE_OK" in out


def test_ep_sharded_dropless_moe_matches_single_device():
    """Dropless grouped dispatch with the expert axis sharded over the
    model (EP) axis of a (2, 2) mesh matches the single-device reference."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import ARCHS
        from repro.models import forward, init_params, synth_batch
        from repro.parallel import sharding as SH
        from jax.sharding import AxisType

        cfg = ARCHS["granite-moe-1b-a400m"].reduced()
        assert cfg.moe_dispatch == "dropless" and cfg.n_experts == 4
        p = init_params(jax.random.PRNGKey(0), cfg)
        batch = synth_batch(jax.random.PRNGKey(1), cfg, 16, 4, "prefill")
        fwd = lambda p, b: forward(p, cfg, b, remat=False)
        h1, _ = jax.jit(fwd)(p, batch)

        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        rules = SH.ShardingRules()
        specs = SH.param_specs(p, rules)
        # the expert axis of the stacked (L, E, D, F) weights rides the
        # model axis (EP): 4 experts over 2 devices
        gspec = specs["groups"][0]["b0"]["ffn"]["w_gate"]
        assert gspec[1] == "model", gspec
        psh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
        bsh = jax.tree.map(
            lambda x: NamedSharding(mesh, P("data", *([None]*(x.ndim-1)))),
            batch)
        h2, _ = jax.jit(fwd, in_shardings=(psh, bsh))(
            jax.device_put(p, psh), jax.device_put(batch, bsh))
        np.testing.assert_allclose(np.asarray(h1, np.float32),
                                   np.asarray(h2, np.float32),
                                   atol=2e-3, rtol=1e-2)
        print("EP_MOE_OK")
    """, n=4)
    assert "EP_MOE_OK" in out


def test_compressed_psum_error_feedback():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.optim.grad import compressed_psum
        from jax.sharding import AxisType
        mesh = jax.make_mesh((4,), ("dp",),
                             axis_types=(AxisType.Auto,))
        g = jax.random.normal(jax.random.PRNGKey(0), (4, 256))

        def f(gs, err):
            m, e = compressed_psum(gs[0], "dp", err[0])
            return m[None], e[None]

        sm = shard_map(f, mesh=mesh, in_specs=(P("dp"), P("dp")),
                       out_specs=(P("dp"), P("dp")), check_rep=False)
        err = jnp.zeros((4, 256))
        total_err = []
        # over steps the error-feedback keeps the cumulative bias bounded
        for _ in range(3):
            mean, err = sm(g, err)
            exact = jnp.mean(g, 0)
            total_err.append(float(jnp.max(jnp.abs(mean[0] - exact))))
        assert total_err[0] < 0.15, total_err
        print("COMPRESS_OK", total_err)
    """, n=4)
    assert "COMPRESS_OK" in out
