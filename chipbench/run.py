#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Builds the cell's PPO experiment the way ``launch/train.build_experiment``
does (``RLHFExperiment`` through ``core/runtime.RuntimeEngine``) on weights
made from the seed, warms up by running whole PPO iterations until the
program's compile count per iteration stops falling, then runs whole
iterations for ``--seconds``.  With
``--trace 0`` the last line of standard output holds the end-to-end metrics;
with ``--trace 1`` the window runs under the profiler, each call inside a
span of its own, and the line holds the per-layer metrics.  After the window
the program's state is freed and the plain reference follows the first
iterations; ``correct`` says whether every compared number kept its limit.

Exits non-zero with no result line where JAX finds no TPU, fewer chips than
the cell asks for, or a device kind missing from ``peaks.json``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
if not __package__:
    # run as a script: its own directory would shadow the standard
    # library's ``trace``
    sys.path[:1] = [str(REPO)]
if str(REPO / "src") not in sys.path:
    sys.path.append(str(REPO / "src"))

import jax  # noqa: E402

from chipbench import cell as C  # noqa: E402
from chipbench import check  # noqa: E402
from chipbench import trace as TR  # noqa: E402
from chipbench import weights as W  # noqa: E402
from chipbench.reference import Reference, follow  # noqa: E402

TRACE_ITERATIONS = 3  # iterations a traced window holds at most
WARMUP_LIMIT = 4  # iterations past the followed ones before giving up
# iterations the reference follows; every cell's limits were read at 3
FOLLOW_ITERATIONS = 3


def log(*args):
    print("chipbench:", *args, file=sys.stderr, flush=True)


def chips_or_exit(count: int) -> tuple[list, dict]:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chipbench: needs a TPU; JAX found {len(devs)} "
                 f"{devs[0].platform!r} device(s).  Nothing was run.")
    if len(devs) < count:
        sys.exit(f"chipbench: the cell needs {count} chips; JAX found "
                 f"{len(devs)}.  Nothing was run.")
    with open(HERE / "peaks.json") as f:
        peaks = json.load(f)
    kind = devs[0].device_kind
    if kind not in peaks:
        sys.exit(f"chipbench: device kind {kind!r} is not in peaks.json "
                 f"({sorted(peaks)}).  Nothing was run.")
    return devs, peaks[kind]


def enable_cache() -> str:
    """JAX's persistent compilation cache at the fixed path
    ``<checkout>/.cache/jax``, every program in it however short its
    compile; returns the directory."""
    from repro.launch.compile_cache import enable_compile_cache
    path = str(REPO / ".cache" / "jax")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return enable_compile_cache()


class CompileCounter:
    """Counts the compilations JAX reports (tracing, lowering, compiling or
    loading a program from the persistent cache)."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.n += 1


def wrap_calls(executors: dict) -> None:
    """Put each executor inside a span named after its call, and block on
    its outputs, so that device time falls inside the span of its call."""
    for name, fn in list(executors.items()):
        def traced(ms, inputs, fn=fn, name=name):
            with jax.profiler.TraceAnnotation(TR.SPAN_PREFIX + name):
                out = fn(ms, inputs)
                jax.block_until_ready([x for x in jax.tree.leaves(out)
                                       if isinstance(x, jax.Array)])
            return out
        executors[name] = traced


def read_trace(logdir: str, show: bool = False) -> TR.Events:
    from jax.profiler import ProfileData
    files = sorted(Path(logdir).rglob("*.xplane.pb"))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {logdir}")
    log(f"trace file {files[-1].stat().st_size} bytes")
    profile = ProfileData.from_file(str(files[-1]))
    if show:
        for plane in profile.planes:
            log(f"plane {plane.name!r}: " + ", ".join(
                f"{ln.name!r} {sum(1 for _ in ln.events)}"
                for ln in plane.lines))
    return TR.from_profile(profile)


def sync(out):
    jax.block_until_ready([x for x in jax.tree.leaves(out)
                           if isinstance(x, jax.Array)])


def load_metric(name: str):
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"),
        HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def warm_up(cell, seed: int, compiles: CompileCounter, settle: bool = True):
    """Build the cell's experiment on the seed's weights and run the
    iterations the reference follows, recording what they produced; with
    ``settle``, go on until an iteration compiles no more than the one
    before it (the program re-lowers the eager scan of its advantage
    estimate in every iteration, so a warm iteration still counts a few).
    Returns (experiment, record, weight key, prompt key, next iteration)."""
    key = W.seed_key(seed)
    wkey, pkey = jax.random.fold_in(key, 1), jax.random.fold_in(key, 2)
    t = time.perf_counter()
    run = C.build(cell, seed, W.make(wkey, cell.family, cell.arch))
    log(f"build (weights, plan search, executors) "
        f"{time.perf_counter() - t:.3f}s")
    follow_n = FOLLOW_ITERATIONS
    prog = {"iterations": []}
    i, last = 0, None
    while True:
        c0, t = compiles.n, time.perf_counter()
        out = run.run_iteration(jax.random.fold_in(pkey, i))
        sync(out)
        count = compiles.n - c0
        log(f"warm-up iteration {i}: {time.perf_counter() - t:.3f}s, "
            f"{count} compiles")
        if i < follow_n:
            prog["iterations"].append(C.record(out))
        if i == 0:
            prog["m"] = C.opt_norms(run, "m")
        if i == follow_n - 1:
            prog["change"] = C.opt_norms(run, "change")
        i += 1
        if i >= follow_n and (not settle or count == last):
            return run, prog, wkey, pkey, i
        last = count
        if i >= follow_n + WARMUP_LIMIT:
            raise RuntimeError(f"iteration {i - 1} still compiled")


def free(run) -> None:
    """Drop the program's state so that the reference has the chip."""
    run.engine.executors.clear()
    run.models.clear()
    gc.collect()
    log(f"program freed; {sum(x.nbytes for x in jax.live_arrays())} bytes "
        "still live")


def follow_reference(cell, wkey, seqs, limit_bytes: int, dot: str = "fp32",
                     fault: str | None = None, programs: dict | None = None
                     ) -> dict:
    """The plain reference over the served tokens ``seqs``; the idle
    model's AdamW moments go to the host where both would not fit.
    ``programs`` keeps the jitted reference across calls in one process."""
    t = time.perf_counter()
    family, arch = cell.family, cell.arch
    programs = {} if programs is None else programs
    key = (cell.name, dot, fault)
    if key not in programs:
        programs[key] = Reference(family, arch, cell.hp, cell.prompt_len,
                                  dot=dot, fault=fault)
    ref = programs[key]
    # two models' bf16 weights, fp32 master and bf16 moments, and room for
    # the gradients and activations of a train step
    need = 20 * arch.param_count("lm") + 8e9
    offload = bool(limit_bytes) and need > limit_bytes
    out = follow(ref, lambda: W.make(wkey, family, arch), seqs,
                 offload=offload)
    log(f"reference ({dot}, fault {fault}) over {len(seqs)} iterations "
        f"{time.perf_counter() - t:.3f}s (idle moments "
        f"{'on the host' if offload else 'on the chip'})")
    return out


def run_cell(cell, seed: int, seconds: float, traced: bool, devs: list,
             peak: dict, dump_trace: str | None = None) -> dict:
    """One run of a cell; returns the result line's object."""
    compiles = CompileCounter()
    run, prog, wkey, pkey, i = warm_up(cell, seed, compiles)
    setup_s = time.perf_counter() - T_START

    logdir = None
    if traced:
        wrap_calls(run.engine.executors)
        logdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(logdir)
    c0, n, failed = compiles.n, 0, 0
    t0 = time.perf_counter()
    while True:
        out = run.run_iteration(jax.random.fold_in(pkey, i + n))
        sync(out)
        n += 1
        if not all(math.isfinite(out[k]["loss"])
                   for k in ("actor_stats", "critic_stats")):
            failed += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds or (traced and n >= TRACE_ITERATIONS):
            break
    del out
    window_compiles = compiles.n - c0
    summary = None
    if traced:
        jax.profiler.stop_trace()
        t = time.perf_counter()
        events = read_trace(logdir, show=bool(dump_trace))
        shutil.rmtree(logdir, ignore_errors=True)
        if dump_trace:
            events.cut().dump(dump_trace)
        summary = TR.reduce(events)
        del events
        log(f"trace read in {time.perf_counter() - t:.3f}s")
    log(f"window: {n} iterations in {elapsed:.3f}s, "
        f"{window_compiles} compiles")

    stats = [d.memory_stats() or {} for d in devs[:cell.chips]]
    peak_bytes = max(s.get("peak_bytes_in_use", 0) for s in stats)
    limit_bytes = min(s.get("bytes_limit", 0) for s in stats)

    free(run)
    del run
    r = follow_reference(cell, wkey, [it["seq"] for it in prog["iterations"]],
                         limit_bytes)
    correct, shown = check.verdict(check.numbers(prog, r), cell.limits)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": n, "failed": failed}
    if traced:
        ctx = types.SimpleNamespace(
            trace=summary, iterations=n, chips=cell.chips, peak=peak,
            costs=cell.costs)
        metrics = {}
        for m in cell.per_layer:
            value = load_metric(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result.update(metrics=metrics, device=device, breakdown={
            "device_ops": summary.device_ops, "idle_gaps": summary.idle_gaps})
    else:
        values = {"setup_s": setup_s, "peak_hbm_gb": peak_bytes / 1e9,
                  "tokens_per_s": n * cell.tokens_per_iteration / elapsed}
        result.update(metrics={
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}, device=device)
    result["checks"] = shown
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default=None,
                    help="also write a cut of the trace's events as JSON here")
    args = ap.parse_args(argv)
    cell = C.load_cell(args.workload)
    devs, peak = chips_or_exit(cell.chips)
    log(f"device {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"compile cache {enable_cache()}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devs,
                      peak, dump_trace=args.dump_trace)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
