"""The program's own spans and the device's module events in a profiler
trace: what the host did while the chip sat idle.

``read`` takes from a ``jax.profiler.ProfileData`` the host spans the
program writes (``repro.core.tracing``: ``rt.*`` in the runtime, ``ppo.*``
in the PPO executors, each with its stats) and the events of each TPU
plane's "XLA Modules" line (one per execution of a compiled program, named
after the jitted function: ``jit_actor_generate(<id>)``).  ``reduce`` adds
them to the benchmark's own ``trace.Events`` (device operations and the
``call:`` spans, which it leaves as they are):

- ``idle_in_span_s``: device-idle seconds inside the union of each span
  name's intervals, and of ``<name>/<call>`` for spans with a ``call`` stat;
- ``idle_outside_s``: device-idle seconds in the window outside every
  ``rt.exec`` (event loop, thread hops, the caller between iterations);
- ``module_device_s``: device-busy seconds inside each program's events;
- ``kernel_device_s``: device seconds of each named Pallas kernel's ops.

The window runs from the first ``rt.iteration`` span's start to the last
one's end.  Spans are intervals with stats, not a tree: at
``pipeline_depth`` > 1 iterations overlap on the loop thread.  ``readings``
turns a ``Breakdown`` and the program's compile counters into the five
per-iteration numbers of ``PERF.md`` section 3.

Run as a tool, it traces a cell's window the way ``run.py --trace 1`` does
(the benchmark's blocking ``call:`` wrapper included, so that its numbers
sit beside these on one trace) and prints both on one line of JSON; the
reference comparison is not run:

    python3 -m chipbench.spans --workload <cell> --seed <n> [--dump FILE]
"""

from __future__ import annotations

import argparse
import bisect
import dataclasses
import json
import re
import sys
import tempfile
import time
import types
from pathlib import Path

import jax
from jax.profiler import ProfileData

from chipbench import cell as C
from chipbench import run as R
from chipbench import trace as TR

PREFIXES = ("rt.", "ppo.")
MODULES_LINE = "XLA Modules"
KERNELS = ("flash_mha", "flash_decode", "paged_flash_decode")


@dataclasses.dataclass
class Program:
    spans: list  # (start_ns, end_ns, name, {stat: value})
    modules: list  # (start_ns, end_ns, program name, device index)

    def dump(self, events: TR.Events, path) -> None:
        with open(path, "w") as f:
            json.dump({"devices": events.devices, "ops": events.ops,
                       "spans": events.spans, "program": self.spans,
                       "modules": self.modules}, f)

    @staticmethod
    def load(path) -> tuple[TR.Events, "Program"]:
        with open(path) as f:
            d = json.load(f)
        return (TR.Events([tuple(o) for o in d["ops"]],
                          [tuple(s) for s in d["spans"]], d["devices"]),
                Program([tuple(s) for s in d["program"]],
                        [tuple(m) for m in d["modules"]]))

    def cut(self, events: TR.Events, per_boundary: int = 20,
            kernel_ops: int = 40) -> tuple[TR.Events, "Program"]:
        """A small piece for tests: device 0's operations nearest each
        boundary of the first iteration's program spans, and the first
        ``kernel_ops`` operations of each named kernel in it, with spans and
        module events clipped to the piece."""
        its = sorted(s for s in self.spans if s[2] == "rt.iteration")
        lo, hi = its[0][0], its[0][1]
        ops = sorted(o for o in events.ops if o[3] == 0)
        starts = [o[0] for o in ops]
        keep = set()
        for s, e, _, _ in self.spans:
            if lo <= s and e <= hi:
                for t in (s, e):
                    i = bisect.bisect_left(starts, t)
                    keep.update(range(max(0, i - per_boundary),
                                      min(len(ops), i + per_boundary)))
        for k in KERNELS:
            hits = [i for i, o in enumerate(ops)
                    if lo <= o[0] < hi and op_kernel(o[2]) == k]
            keep.update(hits[:kernel_ops])
        ops = [ops[i] for i in sorted(keep)]
        t0, t1 = ops[0][0], max(o[1] for o in ops)

        def clip(items):
            return [(max(x[0], t0), min(x[1], t1), *x[2:]) for x in items
                    if x[1] > t0 and x[0] < t1]
        return (TR.Events(ops, clip(events.spans), 1),
                Program(clip(self.spans),
                        clip([m for m in self.modules if m[3] == 0])))


def read(profile) -> Program:
    spans, modules, dev = [], [], 0
    for plane in profile.planes:
        name = plane.name
        if name.startswith("/device:TPU:") and name[12:].isdigit():
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for ev in line.events:
                        modules.append((round(ev.start_ns), round(ev.end_ns),
                                        ev.name, dev))
            dev += 1
        elif name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIXES):
                        spans.append((round(ev.start_ns), round(ev.end_ns),
                                      ev.name, dict(ev.stats)))
    return Program(sorted(spans, key=lambda s: s[:3]), sorted(modules))


_ID = re.compile(r"\(\d+\)$")


def module_name(event_name: str) -> str:
    """``jit_actor_generate(123)`` -> ``jit_actor_generate``."""
    return _ID.sub("", event_name)


_OP = re.compile(r"^%?(.*?)(\.\d+)?$")


def op_kernel(op_name: str) -> str:
    """``%flash_decode.8`` -> ``flash_decode``."""
    return _OP.match(op_name).group(1)


@dataclasses.dataclass
class Breakdown:
    iterations: int  # rt.iteration spans in the window
    window_s: float
    idle_in_span_s: dict
    idle_outside_s: float
    module_device_s: dict
    kernel_device_s: dict


def _clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if min(e, t1) > max(s, t0)]


def _idle_inside(busy, spans) -> int:
    """ns of the union of ``spans`` in which device 0 ran nothing."""
    return sum(b - a - TR.overlap(busy, a, b) for a, b in TR.merge(spans))


def reduce(ev: TR.Events, prog: Program) -> Breakdown:
    its = [s for s in prog.spans if s[2] == "rt.iteration"]
    if not its:
        raise ValueError("the trace holds no rt.iteration span")
    t0, t1 = min(s[0] for s in its), max(s[1] for s in its)
    per_dev = {d: [] for d in range(ev.devices)}
    kernel = {}
    for s, e, name, d in ev.ops:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            per_dev[d].append((s, e))
            k = op_kernel(name)
            if k in KERNELS:
                kernel[k] = kernel.get(k, 0) + (e - s)
    busy = {d: TR.merge(v) for d, v in per_dev.items()}

    groups = {}
    for s, e, name, stats in prog.spans:
        keys = [name] + ([f"{name}/{stats['call']}"] if "call" in stats
                         else [])
        for k in keys:
            groups.setdefault(k, []).append((s, e))

    def idle(spans):  # averaged over devices, seconds
        spans = _clip(spans, t0, t1)
        return sum(_idle_inside(busy[d], spans)
                   for d in busy) / ev.devices * 1e-9

    outside = [(t0, t1)]
    for a, b in TR.merge(_clip(groups.get("rt.exec", []), t0, t1)):
        outside = [(x, y) for s, e in outside
                   for x, y in ((s, min(e, a)), (max(s, b), e)) if y > x]
    modules = {}
    for s, e, name, d in prog.modules:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            n = module_name(name)
            modules[n] = modules.get(n, 0) + TR.overlap(busy[d], s, e)
    return Breakdown(
        iterations=len(its), window_s=(t1 - t0) * 1e-9,
        idle_in_span_s={k: idle(v) for k, v in sorted(groups.items())},
        idle_outside_s=idle(outside),
        module_device_s={k: v / ev.devices * 1e-9
                         for k, v in sorted(modules.items())},
        kernel_device_s={k: v / ev.devices * 1e-9
                         for k, v in sorted(kernel.items())})


def counter_delta(before: dict, after: dict) -> dict:
    """The change of ``RuntimeEngine.stats()["calls"]``'s compile counts
    between two readings: {call: {"traces", "lowerings", "compile_s"}}."""
    keys = ("traces", "lowerings", "compile_s")
    return {c: {k: row.get(k, 0) - before.get(c, {}).get(k, 0)
                for k in keys}
            for c, row in after.items() if "lowerings" in row}


def readings(b: Breakdown, counters: dict | None = None) -> dict:
    """The per-iteration numbers; a number whose source the trace or the
    program lacks is left out."""
    n = b.iterations
    out = {}
    if "jit_actor_generate" in b.module_device_s:
        out["device_ms.actor_gen"] = (
            1e3 * b.module_device_s["jit_actor_generate"] / n)
    if "ppo.adv" in b.idle_in_span_s:
        out["idle_ms.adv"] = 1e3 * b.idle_in_span_s["ppo.adv"] / n
    out["idle_ms.runtime"] = 1e3 * b.idle_outside_s / n
    if counters:
        out["compiles_per_iter"] = sum(
            row["lowerings"] for row in counters.values()) / n
    if "flash_decode" in b.kernel_device_s:
        out["device_ms.flash_decode"] = (
            1e3 * b.kernel_device_s["flash_decode"] / n)
    return out


# ------------------------------------------------------------- the tool

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--dump", default=None,
                    help="write a cut of the trace with program spans here")
    args = ap.parse_args(argv)
    cell = C.load_cell(args.workload)
    devs, peak = R.chips_or_exit(cell.chips)
    R.log(f"device {devs[0].device_kind} x{len(devs)}; "
          f"compile cache {R.enable_cache()}")
    compiles = R.CompileCounter()
    run, _, _, pkey, i = R.warm_up(cell, args.seed, compiles)
    before = run.engine.stats()["calls"]
    R.wrap_calls(run.engine.executors)
    logdir = tempfile.mkdtemp(prefix="chipbench-spans-")
    jax.profiler.start_trace(logdir)
    t = time.perf_counter()
    for n in range(R.TRACE_ITERATIONS):
        R.sync(run.run_iteration(jax.random.fold_in(pkey, i + n)))
    elapsed = time.perf_counter() - t
    jax.profiler.stop_trace()
    counters = counter_delta(before, run.engine.stats()["calls"])
    path = sorted(Path(logdir).rglob("*.xplane.pb"))[-1]
    profile = ProfileData.from_file(str(path))
    events, prog = TR.from_profile(profile), read(profile)
    del profile
    if args.dump:
        cut_events, cut_prog = prog.cut(events)
        cut_prog.dump(cut_events, args.dump)
    b = reduce(events, prog)
    summary = TR.reduce(events)
    ctx = types.SimpleNamespace(
        trace=summary, iterations=R.TRACE_ITERATIONS, chips=cell.chips,
        peak=peak, costs=cell.costs)
    existing = {m["name"]: R.load_metric(m["name"]).read(ctx)
                for m in cell.per_layer}
    print(json.dumps({
        "workload": cell.name, "seed": args.seed,
        "device": devs[0].device_kind,
        "iteration_s": elapsed / R.TRACE_ITERATIONS,
        "readings": readings(b, counters), "existing": existing,
        "call_device_ms": {k: 1e3 * v / R.TRACE_ITERATIONS
                           for k, v in summary.call_device_s.items()},
        "breakdown": dataclasses.asdict(b), "counters": counters,
        "idle_gaps": summary.idle_gaps}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
