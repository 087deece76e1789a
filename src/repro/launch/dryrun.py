import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST precede any jax-importing module: jax locks the device count at init.
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import hw  # noqa: E402
from repro.configs import ARCHS, ASSIGNED, SHAPES, cell_supported, get_config  # noqa: E402
from repro.launch import roofline as RL  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import model as MDL  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.parallel import ctx  # noqa: E402
from repro.parallel import sharding as SH  # noqa: E402
from repro.parallel import steps as ST  # noqa: E402

ARTIFACTS = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

"""Multi-pod dry run: lower + compile every (arch x shape) cell on the
single-pod (16,16) and two-pod (2,16,16) meshes, record memory/cost analyses
and HLO collective statistics, and derive the roofline terms (§Roofline).

Artifacts are cached as JSON per cell so repeated runs are incremental.
"""


def batch_axes(multi_pod: bool):
    return ("pod", "data") if multi_pod else ("data",)


def _batch_spec(bsz: int, mesh, ax):
    total = 1
    for a in ax:
        total *= mesh.shape[a]
    if bsz % total == 0:
        return ax if len(ax) > 1 else ax[0]
    if bsz == 1:
        return None
    # shard over as many axes as divide the batch
    if bsz % mesh.shape["data"] == 0:
        return "data"
    return None


def _cache_specs_tree(cache_shapes, bspec, seq_shard: bool):
    """KV caches: (n, B, S, Hkv*Dh) — batch over data axes (or, for batch-1
    long-context cells, the sequence axis: sequence parallelism), innermost
    dim over the TP axis when divisible; recurrent states likewise."""

    def spec(path, x):
        parts = [None] * x.ndim
        if x.ndim >= 2 and x.shape[1] > 1:
            parts[1] = bspec
        if x.ndim >= 4:
            if path[-1].key in ("k", "v") and seq_shard \
                    and x.shape[1] == 1 and x.shape[2] % 16 == 0 \
                    and x.shape[2] >= 4096:
                parts[2] = "data"
            if x.shape[-1] % 16 == 0:
                parts[-1] = "model"
        return P(*parts)

    return jax.tree_util.tree_map_with_path(spec, cache_shapes)


@dataclasses.dataclass
class CellSpec:
    arch: str
    shape: str
    multi_pod: bool
    variant: str = "base"

    @property
    def key(self) -> str:
        pod = "pod2" if self.multi_pod else "pod1"
        v = "" if self.variant == "base" else f"__{self.variant}"
        return f"{self.arch}__{self.shape}__{pod}{v}"


# §Perf hillclimb variants: each changes ONE lever of the execution strategy.
#   micro<k>    grad-accumulation microbatches (live-activation memory)
#   no_fsdp     params replicated over data (kills per-step param all-gathers
#               — the decode-cell fix)
#   fsdp_model  no TP: params ZeRO-sharded over the model axis, pure DP
#               activations (kills per-layer TP all-reduces — tiny-model fix)
#   dp_all      batch sharded over BOTH axes (max DP), params replicated
VARIANTS = ("base", "micro4", "micro16", "micro32", "no_fsdp",
            "fsdp_model", "dp_all", "dp_zero1")


def _variant_setup(cell: CellSpec, mesh):
    pod = "pod" if cell.multi_pod else None
    v = cell.variant
    n_micro = {"micro4": 4, "micro16": 16, "micro32": 32}.get(v, 1)
    if v == "no_fsdp":
        rules = SH.ShardingRules(tp_axis="model", fsdp_axis=None,
                                 pod_axis=pod)
        batch_ax = batch_axes(cell.multi_pod)
    elif v == "fsdp_model":
        rules = SH.ShardingRules(tp_axis=None, fsdp_axis="model",
                                 pod_axis=pod)
        batch_ax = batch_axes(cell.multi_pod)
    elif v in ("dp_all", "dp_zero1"):
        rules = SH.ShardingRules(tp_axis=None, fsdp_axis=None, pod_axis=pod)
        batch_ax = (("pod",) if cell.multi_pod else ()) + ("data", "model")
    else:
        rules = SH.ShardingRules(pod_axis=pod)
        batch_ax = batch_axes(cell.multi_pod)
    return rules, batch_ax, n_micro


def build_and_lower(cell: CellSpec, n_micro: int = 1, extra_tag: str = ""):
    cfg = get_config(cell.arch)
    shape = SHAPES[cell.shape]
    mesh = make_production_mesh(multi_pod=cell.multi_pod)
    rules, b_axes, v_micro = _variant_setup(cell, mesh)
    n_micro = max(n_micro, v_micro)
    ns = lambda s: NamedSharding(mesh, s)

    def with_ctx(fn):
        def wrapped(*a, **k):
            with ctx.use(mesh, b_axes, rules.tp_axis):
                return fn(*a, **k)
        return wrapped

    params_shapes = jax.eval_shape(
        lambda k: MDL.init_params(k, cfg), jax.random.PRNGKey(0))
    pspecs = SH.sanitize_specs(SH.param_specs(params_shapes, rules),
                               params_shapes, mesh)
    psh = jax.tree.map(ns, pspecs)

    bspec = _batch_spec(shape.global_batch, mesh, b_axes)
    kind = shape.kind

    if kind == "train":
        opt_cfg = adamw.AdamWConfig()
        opt_shapes = jax.eval_shape(lambda p: adamw.init(opt_cfg, p),
                                    params_shapes)
        if cell.variant == "dp_zero1":
            # ZeRO-1: shard optimizer states over the data axis (params stay
            # replicated for pure-DP compute; update gathers once per step)
            z1 = SH.ShardingRules(tp_axis=None, fsdp_axis=None,
                                  pod_axis="data")
            ospecs = SH.opt_state_specs(pspecs, z1, params_shapes,
                                        pod_size=mesh.shape["data"])
        else:
            ospecs = SH.opt_state_specs(pspecs, rules, params_shapes,
                                        pod_size=mesh.shape.get("pod", 2))
        ospecs = SH.sanitize_specs(ospecs, opt_shapes, mesh)
        osh = jax.tree.map(ns, ospecs)
        in_specs = MDL.input_specs(cfg, shape.seq_len, shape.global_batch,
                                   "train")
        bsh = jax.tree.map(
            lambda x: ns(P(bspec, *([None] * (x.ndim - 1)))), in_specs)
        step = with_ctx(ST.make_train_step(cfg, opt_cfg, impl="reference",
                                           remat=True, n_micro=n_micro))
        jitted = jax.jit(step, in_shardings=(psh, osh, bsh),
                         out_shardings=(psh, osh, None),
                         donate_argnums=(0, 1))
        args = (params_shapes, opt_shapes, in_specs)
    elif kind == "prefill":
        in_specs = MDL.input_specs(cfg, shape.seq_len, shape.global_batch,
                                   "prefill")
        bsh = jax.tree.map(
            lambda x: ns(P(bspec, *([None] * (x.ndim - 1)))), in_specs)
        step = with_ctx(ST.make_prefill_step(cfg, impl="reference",
                                             extra_len=1))
        jitted = jax.jit(step, in_shardings=(psh, bsh))
        args = (params_shapes, in_specs)
    else:  # decode
        bsz = shape.global_batch
        cache_shapes = ST.cache_specs(cfg, bsz, shape.seq_len + 1)
        cspecs = _cache_specs_tree(cache_shapes, bspec,
                                   seq_shard=(cell.shape == "long_500k"))
        csh = jax.tree.map(ns, cspecs)
        tok = jax.ShapeDtypeStruct((bsz,), jnp.int32)
        tok_sh = ns(P(bspec))
        step = with_ctx(ST.make_decode_step(cfg, impl="reference"))
        jitted = jax.jit(step,
                         in_shardings=(psh, tok_sh, csh, None),
                         out_shardings=(None, csh),
                         donate_argnums=(2,))
        args = (params_shapes, tok, cache_shapes,
                jax.ShapeDtypeStruct((), jnp.int32))

    lowered = jitted.lower(*args)
    return lowered, cfg, shape, mesh


# ------------------------------------------------------- superblock probes

def probe_costs(cell: CellSpec):
    """Per-superblock fwd (and train fwd+bwd) costs under the same shardings,
    used to correct cost_analysis' count-while-once behaviour."""
    cfg = get_config(cell.arch)
    shape = SHAPES[cell.shape]
    mesh = make_production_mesh(multi_pod=cell.multi_pod)
    rules, b_axes, _ = _variant_setup(cell, mesh)
    ns = lambda s: NamedSharding(mesh, s)
    bspec = _batch_spec(shape.global_batch, mesh, b_axes)

    out = []
    for specs, n in T.groups_of(cfg):
        if n <= 1:
            out.append({"trip": n, "fwd": None, "train": None})
            continue
        block_shapes = jax.eval_shape(
            lambda k: {f"b{i}": T.block_init(k, cfg, s)
                       for i, s in enumerate(specs)}, jax.random.PRNGKey(0))
        # param specs: same rules, no stack dim (path lacks "groups" already)
        bspecs = SH.sanitize_specs(SH.param_specs(block_shapes, rules),
                                   block_shapes, mesh)
        bsh = jax.tree.map(ns, bspecs)

        if shape.kind == "decode":
            bsz = shape.global_batch
            x = jax.ShapeDtypeStruct((bsz, 1, cfg.d_model), jnp.dtype(cfg.dtype))
            cache_shapes = jax.eval_shape(
                lambda: T.group_cache_init(cfg, specs, 1, bsz,
                                           shape.seq_len + 1,
                                           jnp.dtype(cfg.dtype)))
            # one layer's stack (1, ...): the layout the decode scan carries
            cspec = _cache_specs_tree(cache_shapes, bspec,
                                      seq_shard=(cell.shape == "long_500k"))
            csh = jax.tree.map(ns, cspec)

            def dec_probe(xx, gp, cache):
                with ctx.use(mesh, b_axes, rules.tp_axis):
                    for i, s in enumerate(specs):
                        xx, cache[f"b{i}"], _ = T.block_decode(
                            gp[f"b{i}"], cfg, s, xx, cache[f"b{i}"],
                            jnp.int32(shape.seq_len - 1), jnp.int32(0),
                            impl="reference")
                    return xx, cache

            j = jax.jit(dec_probe,
                        in_shardings=(ns(P(bspec, None, None)), bsh, csh))
            comp = j.lower(x, block_shapes, cache_shapes).compile()
            ca = comp.cost_analysis()
            out.append({"trip": n,
                        "fwd": {"flops": ca.get("flops", 0.0),
                                "bytes": ca.get("bytes accessed", 0.0)},
                        "train": None,
                        "hlo": comp.as_text()})
            continue

        bsz, sl = shape.global_batch, shape.seq_len
        x = jax.ShapeDtypeStruct((bsz, sl, cfg.d_model), jnp.dtype(cfg.dtype))
        xsh = ns(P(bspec, None, None))

        def fwd_probe(xx, gp):
            with ctx.use(mesh, b_axes, rules.tp_axis):
                pos = jnp.arange(sl)[None, :]
                xx = ctx.constrain(xx, ctx.BATCH, None, None)
                for i, s in enumerate(specs):
                    xx, *_ = T.block_apply(gp[f"b{i}"], cfg, s, xx, pos,
                                           impl="reference")
                return xx

        j = jax.jit(fwd_probe, in_shardings=(xsh, bsh))
        comp = j.lower(x, block_shapes).compile()
        ca = comp.cost_analysis()
        fwd = {"flops": ca.get("flops", 0.0),
               "bytes": ca.get("bytes accessed", 0.0)}

        train = None
        if shape.kind == "train":
            def train_probe(xx, gp):
                f = jax.checkpoint(fwd_probe, prevent_cse=False)
                l, grads = jax.value_and_grad(
                    lambda g: jnp.sum(f(xx, g).astype(jnp.float32)))(gp)
                return l, grads
            j2 = jax.jit(train_probe, in_shardings=(xsh, bsh))
            comp2 = j2.lower(x, block_shapes).compile()
            ca2 = comp2.cost_analysis()
            train = {"flops": ca2.get("flops", 0.0),
                     "bytes": ca2.get("bytes accessed", 0.0)}
        out.append({"trip": n, "fwd": fwd, "train": train})
    return out


# ------------------------------------------------------------- cell runner

def run_cell(cell: CellSpec, *, n_micro: int = 1, with_probes: bool = True,
             save: bool = True) -> dict:
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    path = ARTIFACTS / f"{cell.key}.json"
    if save and path.exists():
        return json.loads(path.read_text())

    cfg = get_config(cell.arch)
    shape = SHAPES[cell.shape]
    ok, why = cell_supported(cfg, shape)
    if not ok:
        res = {"cell": dataclasses.asdict(cell), "skipped": True, "why": why}
        if save:
            path.write_text(json.dumps(res, indent=1))
        return res

    t0 = time.time()
    lowered, cfg, shape, mesh = build_and_lower(cell, n_micro=n_micro)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    hlo = compiled.as_text()
    colls = RL.parse_collectives(hlo)

    flops = float(ca.get("flops", 0.0))
    bytes_ = float(ca.get("bytes accessed", 0.0))
    probes = []
    if with_probes:
        probes = probe_costs(cell)
        for pr in probes:
            body = pr["train"] if (shape.kind == "train" and pr["train"]) \
                else pr["fwd"]
            if body and pr["trip"] > 1:
                flops += (pr["trip"] - 1) * float(body["flops"])
                bytes_ += (pr["trip"] - 1) * float(body["bytes"])
            pr.pop("hlo", None)

    n_chips = mesh.devices.size
    mf = RL.model_flops(cfg, shape.kind, shape.global_batch, shape.seq_len)
    terms = RL.RooflineTerms(flops, bytes_, colls.total_wire_bytes,
                             hw.V5E, model_flops_total=mf, n_chips=n_chips)

    res = {
        "cell": dataclasses.asdict(cell),
        "skipped": False,
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_per_device": (ma.argument_size_in_bytes
                                + ma.output_size_in_bytes
                                + ma.temp_size_in_bytes
                                - ma.alias_size_in_bytes),
            "hbm_per_device": hw.V5E.hbm_bytes,
        },
        "cost": {"flops_raw": float(ca.get("flops", 0.0)),
                 "bytes_raw": float(ca.get("bytes accessed", 0.0)),
                 "flops_corrected": flops, "bytes_corrected": bytes_},
        "collectives": {
            "counts": colls.counts,
            "bytes_by_kind": colls.bytes_by_kind,
            "wire_bytes_by_kind": colls.wire_bytes_by_kind,
            "total_wire_bytes": colls.total_wire_bytes,
        },
        "probes": probes,
        "model_flops": mf,
        "roofline": terms.row(),
        "terms": {"flops_per_dev": flops, "hbm_bytes_per_dev": bytes_,
                  "wire_bytes_per_dev": colls.total_wire_bytes},
    }
    if save:
        path.write_text(json.dumps(res, indent=1))
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["pod1", "pod2", "both"], default="both")
    ap.add_argument("--variant", default="base", choices=VARIANTS)
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--micro", type=int, default=1)
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ASSIGNED
    shapes = [args.shape] if args.shape else list(SHAPES)
    pods = {"pod1": [False], "pod2": [True], "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shp in shapes:
            for mp in pods:
                cell = CellSpec(arch, shp, mp, args.variant)
                if args.force:
                    (ARTIFACTS / f"{cell.key}.json").unlink(missing_ok=True)
                try:
                    t0 = time.time()
                    res = run_cell(cell, n_micro=args.micro,
                                   with_probes=not args.no_probes)
                    if res.get("skipped"):
                        print(f"SKIP {cell.key}: {res['why']}")
                        continue
                    r = res["roofline"]
                    mem = res["memory"]["peak_per_device"] / 2**30
                    print(f"OK   {cell.key}: compile={res['compile_s']:.0f}s "
                          f"mem/dev={mem:.2f}GiB dominant={r['dominant']} "
                          f"[comp={r['compute_s']*1e3:.1f}ms "
                          f"mem={r['memory_s']*1e3:.1f}ms "
                          f"coll={r['collective_s']*1e3:.1f}ms] "
                          f"roofline={r['roofline_fraction']:.2%} "
                          f"({time.time()-t0:.0f}s)")
                except Exception as e:  # noqa: BLE001
                    failures.append((cell.key, repr(e)))
                    print(f"FAIL {cell.key}: {e}")
                    traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} cells failed: "
                         + ", ".join(k for k, _ in failures))
    print("all requested cells passed")


if __name__ == "__main__":
    main()
