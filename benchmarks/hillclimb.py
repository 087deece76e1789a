"""§Perf hillclimbing driver: hypothesis -> change -> re-lower -> measure.

Each entry in CLIMBS is one iteration on one of the three chosen cells.
Results (before/after roofline terms) are printed as CSV and appended to
artifacts/hillclimb.json for the EXPERIMENTS.md log.

The flash-kernel adjustment is *measured*, not hand-waved: the superblock
probe is compiled twice — reference attention vs. a traffic-free stub — and
the delta is the naive-attention HBM traffic that the (interpret-validated)
Pallas flash kernel eliminates on the TPU target; the kernel's true streams
(q/k/v/o + dq/dk/dv in bwd) are added back analytically.
"""

from __future__ import annotations

import json
import pathlib

ART = pathlib.Path(__file__).resolve().parents[1] / "artifacts"


def _flash_adjust(cell_key: str, arch: str, shape_name: str, res: dict):
    """Measure attention traffic via the stub probe and produce the
    kernel-adjusted memory term."""
    import os
    assert os.environ.get("XLA_FLAGS", "").find("512") >= 0
    import jax
    from jax.sharding import NamedSharding
    import jax.numpy as jnp
    from repro.configs import SHAPES, get_config
    from repro.launch.dryrun import CellSpec, _batch_spec, _variant_setup
    from repro.launch.mesh import make_production_mesh
    from repro.models import transformer as T
    from repro.parallel import ctx
    from repro.parallel import sharding as SH
    from repro import hw

    cell = CellSpec(arch, shape_name, False)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=False)
    rules, b_axes, _ = _variant_setup(cell, mesh)
    ns = lambda s: NamedSharding(mesh, s)
    bspec = _batch_spec(shape.global_batch, mesh, b_axes)

    specs, n = T.groups_of(cfg)[0]
    block_shapes = jax.eval_shape(
        lambda k: {f"b{i}": T.block_init(k, cfg, s)
                   for i, s in enumerate(specs)}, jax.random.PRNGKey(0))
    bsh = jax.tree.map(ns, SH.sanitize_specs(
        SH.param_specs(block_shapes, rules), block_shapes, mesh))
    bsz, sl = shape.global_batch, shape.seq_len
    x = jax.ShapeDtypeStruct((bsz, sl, cfg.d_model), jnp.dtype(cfg.dtype))
    xsh = ns(jax.sharding.PartitionSpec(bspec, None, None))

    def make_probe(impl):
        def probe(xx, gp):
            with ctx.use(mesh, b_axes, rules.tp_axis):
                xx = ctx.constrain(xx, ctx.BATCH, None, None)
                f = jax.checkpoint(
                    lambda xx, gp: _fwd(xx, gp), prevent_cse=False)
                l, grads = jax.value_and_grad(
                    lambda g: jnp.sum(f(xx, g).astype(jnp.float32)))(gp)
                return l, grads

        def _fwd(xx, gp):
            pos = jnp.arange(sl)[None, :]
            for i, s in enumerate(specs):
                xx, *_ = T.block_apply(gp[f"b{i}"], cfg, s, xx, pos,
                                       impl=impl)
            return xx
        return probe

    def cost_of(impl):
        comp = jax.jit(make_probe(impl), in_shardings=(xsh, bsh)).lower(
            x, block_shapes).compile()
        ca = comp.cost_analysis()
        return float(ca.get("flops", 0)), float(ca.get("bytes accessed", 0))

    f_ref, b_ref = cost_of("reference")
    f_stub, b_stub = cost_of("stub")
    attn_bytes_per_block = b_ref - b_stub
    attn_flops_per_block = f_ref - f_stub

    # flash kernel's true HBM streams for the same work (fwd+bwd, per block):
    # q,k,v,o read/write fwd (4) + bwd reads q,k,v,do + writes dq,dk,dv (7)
    n_dev = mesh.devices.size
    tokens_dev = bsz * sl / (mesh.shape["data"])
    per_tensor = tokens_dev * cfg.q_dim * 2  # bf16, model-axis sharded q_dim
    flash_bytes_per_block = 11 * per_tensor / mesh.shape["model"] * len(
        [s for s in specs if s.kind == "attn"])

    total_attn_bytes = attn_bytes_per_block * n
    total_flash_bytes = flash_bytes_per_block * n
    adj_bytes = (res["terms"]["hbm_bytes_per_dev"] - total_attn_bytes
                 + total_flash_bytes)
    return {
        "attn_bytes_per_dev": total_attn_bytes,
        "attn_flops_per_dev": attn_flops_per_block * n,
        "flash_bytes_per_dev": total_flash_bytes,
        "memory_s_flash_adjusted": adj_bytes / hw.V5E.hbm_bw,
        "memory_s_before": res["roofline"]["memory_s"],
    }


def _dus_adjust(arch: str, shape_name: str, variant: str = "base"):
    """Decode cells: cost_analysis charges dynamic-update-slice as a full
    cache read+write, but donated caches update in place on TPU (and the
    flash_decode kernel writes only the new slot).  Parse the HLO, subtract
    full-operand DUS bytes, add the true slice bytes."""
    import re
    from repro.launch.dryrun import CellSpec, build_and_lower
    from repro.launch.roofline import (_split_computations, _while_info,
                                       _reachable, _largest_tensor)
    from repro import hw

    cell = CellSpec(arch, shape_name, False, variant)
    lowered, cfg, shape, mesh = build_and_lower(cell)
    comp = lowered.compile()
    ca = comp.cost_analysis()
    hlo = comp.as_text()
    comps = _split_computations(hlo)
    whiles = _while_info(hlo, comps)
    mult = {name: 1.0 for name in comps}
    for body, cond, trip in whiles:
        for c in _reachable(comps, body):
            mult[c] = mult.get(c, 1.0) * (trip or 1)
    dus_bytes = 0.0
    for name, lines in comps.items():
        for line in lines:
            if "dynamic-update-slice" in line and "fused" not in line:
                dus_bytes += 2.0 * _largest_tensor(line) * mult.get(name, 1.0)
    raw = float(ca.get("bytes accessed", 0.0))
    return {"bytes_raw": raw, "dus_bytes": dus_bytes,
            "memory_s_raw": raw / hw.V5E.hbm_bw,
            "memory_s_dus_adjusted": (raw - dus_bytes) / hw.V5E.hbm_bw}


def run_climbs(climbs):
    """climbs: list of (arch, shape, variant, hypothesis)."""
    from repro.launch.dryrun import CellSpec, run_cell
    out = []
    for arch, shape, variant, hyp in climbs:
        cell = CellSpec(arch, shape, False, variant)
        res = run_cell(cell, with_probes=True)
        row = {
            "cell": cell.key, "variant": variant, "hypothesis": hyp,
            "roofline": res["roofline"],
            "mem_gib": res["memory"]["peak_per_device"] / 2**30,
            "compile_s": res["compile_s"],
        }
        out.append(row)
        r = res["roofline"]
        print(f"{cell.key}: dom={r['dominant']} comp={r['compute_s']*1e3:.0f}ms "
              f"mem={r['memory_s']*1e3:.0f}ms coll={r['collective_s']*1e3:.0f}ms "
              f"frac={r['roofline_fraction']:.3f} "
              f"mem_gib={row['mem_gib']:.1f}", flush=True)
    return out


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--flash-adjust", nargs=2, metavar=("ARCH", "SHAPE"),
                    default=None)
    ap.add_argument("--dus-adjust", nargs=2, metavar=("ARCH", "SHAPE"),
                    default=None)
    ap.add_argument("--climb", nargs=3, metavar=("ARCH", "SHAPE", "VARIANT"),
                    action="append", default=[])
    args = ap.parse_args()

    results = []
    if args.dus_adjust:
        arch, shape = args.dus_adjust
        adj = _dus_adjust(arch, shape)
        print(json.dumps(adj, indent=1))
        results.append({"cell": f"{arch}__{shape}__pod1", "dus_adjust": adj})
    if args.flash_adjust:
        arch, shape = args.flash_adjust
        from repro.launch.dryrun import CellSpec, run_cell
        res = run_cell(CellSpec(arch, shape, False))
        adj = _flash_adjust(f"{arch}__{shape}", arch, shape, res)
        print(json.dumps(adj, indent=1))
        results.append({"cell": f"{arch}__{shape}__pod1",
                        "flash_adjust": adj})
    if args.climb:
        results += run_climbs([(a, s, v, "") for a, s, v in args.climb])

    path = ART / "hillclimb.json"
    prev = json.loads(path.read_text()) if path.exists() else []
    path.write_text(json.dumps(prev + results, indent=1))


if __name__ == "__main__":
    import os
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=512")
    main()
