"""Reduction of a profiler trace to device busy time, idle gaps and the
device time of each call.

``from_profile`` reads a ``jax.profiler.ProfileData``: the operations on the
"XLA Ops" line of each device plane, and the host spans the benchmark wrote
around each call (``jax.profiler.TraceAnnotation`` named ``call:<name>``).
``reduce`` works on those lists alone, so a test can feed it a recorded
trace.  Times are nanoseconds on the profiler's clock.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
from collections import defaultdict

SPAN_PREFIX = "call:"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Events:
    ops: list  # (start_ns, end_ns, op name, device index)
    spans: list  # (start_ns, end_ns, call name)
    devices: int

    def dump(self, path):
        with open(path, "w") as f:
            json.dump({"devices": self.devices, "ops": self.ops,
                       "spans": self.spans}, f)

    def cut(self, per_boundary: int = 40) -> "Events":
        """A small piece of the trace for tests: the device operations
        nearest each boundary between the first iteration's calls, with
        the spans clipped to the piece."""
        ops = sorted(o for o in self.ops if o[3] == 0)
        starts = [o[0] for o in ops]
        keep = set()
        for _, end, _ in self.spans[:6]:
            i = bisect.bisect_left(starts, end)
            keep.update(range(max(0, i - per_boundary),
                              min(len(ops), i + per_boundary)))
        ops = [ops[i] for i in sorted(keep)]
        t0, t1 = ops[0][0], max(o[1] for o in ops)
        spans = [(max(s, t0), min(e, t1), n) for s, e, n in self.spans
                 if e > t0 and s < t1]
        return Events(ops, spans, 1)

    @classmethod
    def load(cls, path) -> "Events":
        with open(path) as f:
            d = json.load(f)
        return cls([tuple(o) for o in d["ops"]],
                   [tuple(s) for s in d["spans"]], d["devices"])


def from_profile(profile) -> Events:
    ops, spans, dev = [], [], 0
    for plane in profile.planes:
        name = plane.name
        if name.startswith("/device:TPU:") and name[12:].isdigit():
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                raise RuntimeError(f"{name} has no {OPS_LINE!r} line; lines: "
                                   f"{sorted(lines)}")
            for ev in lines[OPS_LINE].events:
                # the name is the HLO instruction's text; keep its name
                ops.append((round(ev.start_ns), round(ev.end_ns),
                            ev.name.split(" = ", 1)[0], dev))
            dev += 1
        elif name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((round(ev.start_ns), round(ev.end_ns),
                                      ev.name[len(SPAN_PREFIX):]))
    if not dev:
        raise RuntimeError("the trace holds no TPU device plane")
    return Events(ops, sorted(spans), dev)


def merge(intervals):
    """Union of (start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap(merged, s, e) -> int:
    return sum(max(0, min(e, b) - max(s, a)) for a, b in merged)


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # averaged over devices
    call_device_s: dict  # call -> device busy seconds inside its spans
    call_span_s: dict  # call -> host seconds of its spans
    device_ops: list  # [[op name, seconds]], most time first
    idle_gaps: list  # [[what the host was in, seconds]], longest first


def reduce(ev: Events, top: int = 10) -> Summary:
    if ev.spans:
        t0 = min(s for s, _, _ in ev.spans)
        t1 = max(e for _, e, _ in ev.spans)
    else:
        t0 = min(o[0] for o in ev.ops)
        t1 = max(o[1] for o in ev.ops)
    per_dev = defaultdict(list)
    op_time = defaultdict(int)
    for s, e, name, d in ev.ops:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            per_dev[d].append((s, e))
            op_time[name] += e - s
    busy = {d: merge(per_dev[d]) for d in range(ev.devices)}
    busy_ns = sum(sum(b - a for a, b in m) for m in busy.values()) / ev.devices

    call_dev = defaultdict(float)
    call_span = defaultdict(float)
    for s, e, name in ev.spans:
        call_dev[name] += sum(overlap(busy[d], s, e)
                              for d in busy) / ev.devices * 1e-9
        call_span[name] += (e - s) * 1e-9

    def where(t):
        last = "start of window"
        for s, e, name in ev.spans:
            if s <= t < e:
                return f"in {name}"
            if e <= t:
                last = f"after {name}"
        return last

    gaps, prev = [], t0
    for a, b in busy[0] + [[t1, t1]]:
        if a > prev:
            gaps.append((a - prev, prev))
        prev = max(prev, b)
    gaps.sort(key=lambda g: (-g[0], g[1]))  # longest first, then earliest
    return Summary(
        window_s=(t1 - t0) * 1e-9, busy_s=busy_ns * 1e-9,
        call_device_s=dict(call_dev), call_span_s=dict(call_span),
        device_ops=[[n, t * 1e-9] for n, t in
                    sorted(op_time.items(), key=lambda x: -x[1])[:top]],
        idle_gaps=[[where(s + g / 2), g * 1e-9] for g, s in gaps[:top]])
