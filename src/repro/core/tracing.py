"""Spans and a compile counter for the runtime and the PPO executors.

``span(name, **stats)`` is a ``jax.profiler.TraceAnnotation``: outside a
profiler session it costs about a microsecond and records nothing; inside
one it writes a host event named ``name`` whose ``stats`` become the event's
stats in the trace, on the profiler's clock, the one the device planes use.
Program spans are named ``rt.*`` (the runtime) and ``ppo.*`` (the PPO
executors); none starts with ``call:``, the prefix a benchmark gives its own
spans around calls.

The compile counter is one process-wide ``jax.monitoring`` listener
(``install``).  It counts JAX's ``/jax/core/compile/*`` events, by kind, for
the ``Compiles`` record that the calling thread has made current with
``attribute``, or for the process's ``outside()`` record when none is.
JAX traces, lowers and compiles in the thread that calls the function, so a
record made current inside a call's executor thread sees exactly that call's
compiles (``run_in_executor`` carries no ``contextvars``, hence a
thread-local).

Program counters follow the same thread-local pattern: ``count(name,
tree)`` adds a tree of (device) arrays into the dict that the calling
thread made current with ``counting``; the runtime makes one current for
each call's executor and adds it into that call's running total
(``RuntimeEngine.counters``), which ``stats()["calls"]`` reports.  The MoE
executors count their routing this way (``moe``: ``routed``, ``held`` and
``held_max`` assignments, each per MoE layer).  Adding costs no host sync;
reading does, in ``stats()``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading

import jax

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"

span = jax.profiler.TraceAnnotation
# a step event: the profiler groups device work by its ``step_num`` stat
step_span = jax.profiler.StepTraceAnnotation


@dataclasses.dataclass
class Compiles:
    """JAX compile events seen while this record was current."""

    traces: int = 0  # jaxprs traced (nested jit traces count once each)
    lowerings: int = 0  # modules lowered to MLIR: one per new program
    # seconds of lowering and backend compilation (a persistent-cache load
    # counts as a backend compile); trace seconds are left out because an
    # inner jit's trace runs inside its caller's
    compile_s: float = 0.0

    def add(self, other: "Compiles") -> None:
        self.traces += other.traces
        self.lowerings += other.lowerings
        self.compile_s += other.compile_s

    def minus(self, other: "Compiles") -> "Compiles":
        return Compiles(self.traces - other.traces,
                        self.lowerings - other.lowerings,
                        self.compile_s - other.compile_s)


_local = threading.local()
_outside = Compiles()
_outside_lock = threading.Lock()  # the listener runs in every thread
_installed = False
_install_lock = threading.Lock()


def _on_event(event: str, duration: float, **_) -> None:
    if event == TRACE_EVENT:
        d = Compiles(traces=1)
    elif event == LOWER_EVENT:
        d = Compiles(lowerings=1, compile_s=duration)
    elif event == BACKEND_EVENT:
        d = Compiles(compile_s=duration)
    else:
        return
    rec = getattr(_local, "compiles", None)
    if rec is not None:  # only this thread touches its current record
        rec.add(d)
    else:
        with _outside_lock:
            _outside.add(d)


def install() -> None:
    """Register the listener once per process."""
    global _installed
    with _install_lock:
        if not _installed:
            jax.monitoring.register_event_duration_secs_listener(_on_event)
            _installed = True


@contextlib.contextmanager
def attribute(rec: Compiles):
    """Count this thread's compile events into ``rec`` inside the block."""
    prev = getattr(_local, "compiles", None)
    _local.compiles = rec
    try:
        yield rec
    finally:
        _local.compiles = prev


@contextlib.contextmanager
def counting(rec: dict):
    """Add this thread's ``count`` calls into ``rec`` inside the block."""
    prev = getattr(_local, "counters", None)
    _local.counters = rec
    try:
        yield rec
    finally:
        _local.counters = prev


def add_counts(total: dict, counts: dict) -> None:
    """Add each of ``counts``' trees into ``total``'s tree of that name."""
    for name, tree in counts.items():
        total[name] = (tree if name not in total
                       else jax.tree.map(lambda a, b: a + b, total[name], tree))


def count(name: str, tree) -> None:
    """Add ``tree`` into the current counters' ``name`` (a no-op where no
    ``counting`` block is current, or for an empty tree)."""
    rec = getattr(_local, "counters", None)
    if rec is not None and jax.tree.leaves(tree):
        add_counts(rec, {name: tree})


def outside() -> Compiles:
    """A copy of the process's count of compiles made outside any
    ``attribute`` block since ``install``."""
    with _outside_lock:
        return dataclasses.replace(_outside)
