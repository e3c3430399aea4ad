"""Execution-trace utilities (paper §5.2: "we use the profiling results to
visualize the execution process ... immensely helpful in analysis"), and the
program's spans on the profiler's clock.

Two kinds of trace live here:

* per-op :class:`TraceEvent` timelines of one host run, rendered by
  :func:`ascii_timeline` / :func:`trace_csv` (times from the run's own
  ``perf_counter`` origin);
* **spans** (``repro.*``): :func:`span` is ``jax.profiler.TraceAnnotation``,
  so a span lands in the same ``.xplane.pb`` as the device's operations and
  shares its clock.  A span is recorded only while a profiler runs; without
  one, opening it costs about a microsecond, so spans stay on with no
  switch.  Span names are built once (module constants, or per node when a
  plan is compiled): the hot path passes no keyword arguments and formats no
  strings.

:func:`install` adds the process-wide parts once: a ``repro.gc`` span around
every garbage collection (from ``gc.callbacks``) and a count of compilations
fed by ``jax.monitoring``.  :func:`name_thread` gives a thread an OS name, so
the profiler's host lines tell the program's threads apart.
"""
from __future__ import annotations

import ctypes
import functools
import gc
import sys
import threading
from typing import Iterable, Sequence

import jax
from jax.profiler import TraceAnnotation as span

from .simulate import TraceEvent

__all__ = [
    "GC_SPAN",
    "NODE_SPAN",
    "PLAN_RUN_SPAN",
    "ProcessTrace",
    "ascii_timeline",
    "install",
    "name_thread",
    "node_span_names",
    "span",
    "trace_csv",
]

PLAN_RUN_SPAN = "repro.plan.run"      # client side of one host-plan run
NODE_SPAN = "repro.plan.node/"        # + node name: one node call
GC_SPAN = "repro.gc"                  # one garbage collection, any thread

# what jax.monitoring reports for a backend compile and for a program loaded
# from the persistent compilation cache instead
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_PR_SET_NAME = 15                     # prctl(2): name the calling thread


def node_span_names(names: Iterable[str]) -> tuple[str, ...]:
    """``repro.plan.node/<name>`` for each node, built once per plan."""
    return tuple(NODE_SPAN + n for n in names)


@functools.cache
def _prctl():
    if not sys.platform.startswith("linux"):
        return None
    try:
        # PyDLL keeps the interpreter lock through the call: a thread that
        # names itself must not hand the lock away and queue to get it back
        fn = ctypes.PyDLL(None, use_errno=True).prctl
    except (OSError, AttributeError):
        return None
    fn.argtypes = [ctypes.c_int, ctypes.c_char_p, ctypes.c_ulong,
                   ctypes.c_ulong, ctypes.c_ulong]
    fn.restype = ctypes.c_int
    return fn


def name_thread(name: str) -> None:
    """Give the calling thread the OS name ``name`` (Linux keeps its first
    15 bytes; elsewhere nothing happens).  The profiler names each host
    line by its thread's OS name, which for every Python thread is
    otherwise the process's own."""
    fn = _prctl()
    if fn is not None:
        fn(_PR_SET_NAME, name.encode()[:15], 0, 0, 0)


class ProcessTrace:
    """The process-wide trace hooks :func:`install` adds once.

    ``n_compiles`` counts backend compilations plus programs loaded from the
    persistent compilation cache, as reported by ``jax.monitoring``, since
    installation; compiles run on whichever thread calls a new program
    (executor threads included), hence the lock.
    """

    def __init__(self):
        self._n_compiles = 0
        self._lock = threading.Lock()
        self._gc_span = None

    @property
    def n_compiles(self) -> int:
        return self._n_compiles

    def _count(self) -> None:
        with self._lock:
            self._n_compiles += 1

    def on_duration(self, event: str, _secs: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            self._count()

    def on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT_EVENT:
            self._count()

    def on_gc(self, phase: str, _info: dict) -> None:
        # a collection runs start to stop on one thread under the
        # interpreter lock, and collections never nest
        if phase == "start":
            self._gc_span = span(GC_SPAN)
            self._gc_span.__enter__()
        elif self._gc_span is not None:
            s, self._gc_span = self._gc_span, None
            s.__exit__(None, None, None)


_installed: ProcessTrace | None = None
_install_lock = threading.Lock()


def install() -> ProcessTrace:
    """Add the ``repro.gc`` span and the compile count to this process, once;
    returns the one :class:`ProcessTrace`."""
    global _installed
    with _install_lock:
        if _installed is None:
            pt = ProcessTrace()
            jax.monitoring.register_event_duration_secs_listener(
                pt.on_duration)
            jax.monitoring.register_event_listener(pt.on_event)
            gc.callbacks.append(pt.on_gc)
            _installed = pt
        return _installed


def ascii_timeline(
    trace: Sequence[TraceEvent], n_executors: int, width: int = 100
) -> str:
    """Render per-executor timelines as ASCII (one row per executor)."""
    if not trace:
        return "(empty trace)"
    t_end = max(e.end for e in trace)
    t_end = t_end or 1.0
    rows = []
    for ex in range(n_executors):
        line = [" "] * width
        for ev in trace:
            if ev.executor != ex:
                continue
            a = int(ev.start / t_end * (width - 1))
            b = max(a + 1, int(ev.end / t_end * (width - 1)))
            ch = ev.op[-1] if ev.op else "#"
            for i in range(a, min(b, width)):
                line[i] = "#" if line[i] != " " else ch
        rows.append(f"E{ex:02d} |" + "".join(line) + "|")
    rows.append(f"     0{' ' * (width - 12)}{t_end * 1e6:9.1f}us")
    return "\n".join(rows)


def trace_csv(trace: Sequence[TraceEvent]) -> str:
    lines = ["op,executor,start_us,end_us,duration_us"]
    for e in sorted(trace, key=lambda e: e.start):
        lines.append(
            f"{e.op},{e.executor},{e.start*1e6:.3f},{e.end*1e6:.3f},{(e.end-e.start)*1e6:.3f}"
        )
    return "\n".join(lines)
