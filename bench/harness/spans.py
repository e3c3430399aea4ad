"""The program's own spans (``repro.*``) in a traced window: the offset between
the host's clock and the device's, the engine's steps paired with their
device work, and the span the engine's main thread was in whenever the
device idled.

The program writes its spans as ``jax.profiler.TraceAnnotation``s (its
``core/trace.py``), so they reach ``trace.Events.host`` beside the
benchmark's ``bench.*`` spans; the window is the ``bench.window`` span.
Every function here reads what it finds: a trace without ``repro.*`` spans
(a program that writes none) gives no steps, no offset and no numbers.

* **Offset.**  The profiler puts device operations on the host's clock, but
  not exactly: read as recorded, a step's first device operation can start
  before any host call could have launched it.  Two causal bounds hold for a
  decode-only step (a ``repro.paged.step`` with a ``repro.paged.decode``
  and no ``repro.paged.chunk``): its device work cannot start before the
  earliest ``repro.plan.node/*`` span of its decode (``lower``), and its
  ``repro.paged.readback``, the step's one wait for the device, cannot end
  before its last device operation (``upper``).  The offset added to device
  times is ``upper``, the smallest readback bound over the window's
  decode-only steps that is not below ``lower``: a readback does nothing
  after the device finishes but copy the sampled tokens, while a launch
  does host work first.
* **Device work of a step.**  Device operations merge into stretches of
  work wherever the device idles for less than ``MERGE_NS``; a decode
  step's stretch is the first one still running when its readback starts.
* **Attribution.**  Each idle instant of the device (in the window, on the
  host's clock) goes to the innermost ``repro.*`` span open on the main
  thread (the thread of ``repro.paged.step``), except that a ``repro.gc``
  span on any thread takes it first: a collection stops every thread.
  Idle time under no such span is unattributed.
"""
from __future__ import annotations

import bisect
from collections import Counter
from dataclasses import dataclass, field

import numpy as np

from .trace import Events

PREFIX = "repro."
STEP = "repro.paged.step"
DECODE = "repro.paged.decode"
READBACK = "repro.paged.readback"
CHUNK = "repro.paged.chunk"
PLAN_RUN = "repro.plan.run"
NODE = "repro.plan.node/"
GC = "repro.gc"
MERGE_NS = 1e5


@dataclass
class Step:
    """One ``repro.paged.step`` of the main thread, and what it holds;
    intervals are (start, end) in ns on the host's clock."""
    t0: float
    t1: float
    decode: tuple | None = None      # repro.paged.decode
    readback: tuple | None = None    # the decode's repro.paged.readback
    plan_run: tuple | None = None    # the decode graph's repro.plan.run
    nodes: list = field(default_factory=list)   # its repro.plan.node/*
    chunks: int = 0                  # repro.paged.chunk spans, any thread
    work: tuple | None = None        # its stretch of device work (device clock)

    @property
    def decode_only(self) -> bool:
        return self.decode is not None and self.chunks == 0


@dataclass
class Clock:
    """Nanoseconds to add to a device time to put it on the host's clock,
    and the two causal bounds it comes from (None where no step gives
    one)."""
    offset: float | None
    lower: float | None
    upper: float | None


@dataclass
class Window:
    lo: float
    hi: float
    steps: list                      # [Step], in order
    clock: Clock
    busy: np.ndarray                 # [n, 2] device busy intervals, device clock
    thread: str                      # the main thread's line

    @property
    def length_ns(self) -> float:
        return self.hi - self.lo


def window(ev: Events) -> tuple[float, float] | None:
    """The ``bench.window`` span, as (start, end) in ns."""
    for name, t0, dur, _ in ev.host:
        if name == "bench.window":
            return t0, t0 + dur
    return None


def main_thread(ev: Events) -> str | None:
    """The host line that holds the ``repro.paged.step`` spans."""
    c = Counter(th for name, _, _, th in ev.host if name == STEP)
    return c.most_common(1)[0][0] if c else None


def _within(spans: list, starts: list, a: float, b: float) -> list:
    """The spans of ``spans`` (sorted by start; ``starts`` their starts)
    that lie inside [a, b]."""
    i = bisect.bisect_left(starts, a)
    out = []
    while i < len(spans) and spans[i][0] <= b:
        if spans[i][1] <= b:
            out.append(spans[i])
        i += 1
    return out


def device_busy(ev: Events, gap: float = 0.0) -> np.ndarray:
    """Busy intervals of the first device, [n, 2] sorted, on the device's
    clock; intervals closer than ``gap`` merge."""
    if not ev.device:
        return np.zeros((0, 2))
    ops = ev.device[sorted(ev.device)[0]]
    if not ops:
        return np.zeros((0, 2))
    t = np.array([(t0, t0 + d) for _, t0, d in ops], dtype=np.float64)
    t = t[np.argsort(t[:, 0], kind="stable")]
    end = np.maximum.accumulate(t[:, 1])
    new = np.ones(len(t), bool)
    new[1:] = t[1:, 0] > end[:-1] + gap
    first = np.flatnonzero(new)
    last = np.r_[first[1:] - 1, len(t) - 1]
    return np.stack([t[first, 0], end[last]], axis=1)


def steps(ev: Events, lo: float, hi: float, thread: str) -> list[Step]:
    """The main thread's steps inside [lo, hi], with their children."""
    by: dict[str, list] = {}
    nodes, chunks = [], []
    for name, t0, dur, th in ev.host:
        if name.startswith(NODE):
            nodes.append((t0, t0 + dur))
        elif name == CHUNK:
            chunks.append((t0, t0 + dur))
        elif th == thread and name in (STEP, DECODE, READBACK, PLAN_RUN):
            by.setdefault(name, []).append((t0, t0 + dur))
    for lst in (*by.values(), nodes, chunks):
        lst.sort()
    starts = {k: [s[0] for s in v] for k, v in by.items()}
    node_starts = [s[0] for s in nodes]
    chunk_starts = [s[0] for s in chunks]
    out = []
    for a, b in by.get(STEP, []):
        if a < lo or b > hi:
            continue
        st = Step(a, b)
        st.chunks = bisect.bisect_right(chunk_starts, b) - bisect.bisect_left(
            chunk_starts, a)
        dec = _within(by.get(DECODE, []), starts.get(DECODE, []), a, b)
        if dec:
            st.decode = dec[0]
            runs = _within(by.get(PLAN_RUN, []), starts.get(PLAN_RUN, []),
                           *st.decode)
            st.plan_run = runs[0] if runs else None
            st.nodes = _within(nodes, node_starts, *st.decode)
            after = [r for r in _within(by.get(READBACK, []),
                                        starts.get(READBACK, []), a, b)
                     if r[0] >= st.decode[1]]
            st.readback = after[0] if after else None
        out.append(st)
    return out


def _pair_work(sts: list[Step], stretches: np.ndarray) -> None:
    """Each decode step's stretch of device work: the first still running
    when its readback starts."""
    if not len(stretches):
        return
    ends = stretches[:, 1]
    for st in sts:
        if st.decode is None or st.readback is None:
            continue
        i = int(np.searchsorted(ends, st.readback[0], side="right"))
        if i < len(stretches):
            st.work = (float(stretches[i, 0]), float(stretches[i, 1]))


def clock(sts: list[Step]) -> Clock:
    """The offset of the device's clock from the host's (module docstring).
    A readback bound below the launch bound contradicts it: that step was
    paired with the wrong work, and its bound is left out."""
    upper = [st.readback[1] - st.work[1] for st in sts
             if st.decode_only and st.work and st.readback]
    # a step's stretch begins with its own first operation only when the
    # step before it left the device idle: a decode-only step does
    lower = [min(n[0] for n in st.nodes) - st.work[0]
             for prev, st in zip(sts, sts[1:])
             if st.decode_only and prev.decode_only and st.work and st.nodes]
    lo = max(lower) if lower else None
    up = min((u for u in upper if lo is None or u >= lo), default=None)
    return Clock(lo if up is None else up, lo, up)


def analyse(ev: Events | None) -> Window | None:
    """Steps, offset and device busy time of the ``bench.window`` of ``ev``;
    None when the trace holds no window or no ``repro.paged.step``."""
    if ev is None:
        return None
    win, thread = window(ev), main_thread(ev)
    if win is None or thread is None:
        return None
    sts = steps(ev, *win, thread)
    _pair_work(sts, device_busy(ev, MERGE_NS))
    return Window(*win, sts, clock(sts), device_busy(ev), thread)


# -- attribution --------------------------------------------------------------
def innermost(spans) -> list[tuple[float, float, str]]:
    """Disjoint (start, end, name) wherever one of ``spans`` ((start, end,
    name), nested as one thread's are) is open, named by the innermost."""
    out, stack, cur = [], [], 0.0
    for t0, t1, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][0] <= t0:
            end, nm = stack.pop()
            out.append((cur, end, nm))
            cur = end
        if stack:
            out.append((cur, t0, stack[-1][1]))
        stack.append((t1, name))
        cur = t0
    while stack:
        end, nm = stack.pop()
        out.append((cur, end, nm))
        cur = end
    return [s for s in out if s[1] > s[0]]


def idle(w: Window) -> list[tuple[float, float]]:
    """The device's idle intervals in the window, on the host's clock."""
    off = w.clock.offset or 0.0
    out, cur = [], w.lo
    for a, b in w.busy + off:
        if b <= cur:
            continue
        if a >= w.hi:
            break
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if cur < w.hi:
        out.append((cur, w.hi))
    return out


def _cover(intervals, segments) -> tuple[dict, list]:
    """Time of sorted disjoint ``intervals`` under each name of sorted
    disjoint ``segments``, and the parts under none."""
    by: dict[str, float] = {}
    rest = []
    starts = [s[0] for s in segments]
    for a, b in intervals:
        cur = a
        j = max(bisect.bisect_right(starts, a) - 1, 0)
        while j < len(segments) and segments[j][0] < b:
            s0, s1, name = segments[j]
            lo, hi = max(s0, cur), min(s1, b)
            if hi > lo:
                if lo > cur:
                    rest.append((cur, lo))
                by[name] = by.get(name, 0.0) + hi - lo
                cur = hi
            j += 1
        if cur < b:
            rest.append((cur, b))
    return by, rest


def attribute(ev: Events, w: Window, gaps=None) -> tuple[dict, float]:
    """Device idle ns of the window (or of ``gaps``, host clock) per
    attributed span name, and the ns left unattributed."""
    main = [(t0, t0 + d, n) for n, t0, d, th in ev.host
            if th == w.thread and n.startswith(PREFIX) and n != GC]
    gc = innermost((t0, t0 + d, n) for n, t0, d, _ in ev.host if n == GC)
    by_gc, rest = _cover(idle(w) if gaps is None else gaps, gc)
    by, rest = _cover(rest, innermost(main))
    for k, v in by_gc.items():
        by[k] = by.get(k, 0.0) + v
    return by, sum(b - a for a, b in rest)


def named_gaps(ev: Events, w: Window, longer_than_ns: float) -> list:
    """Every device idle gap of the window longer than ``longer_than_ns``:
    (start ns, length s, {span: s}, unattributed s), longest first."""
    out = []
    for a, b in idle(w):
        if b - a > longer_than_ns:
            by, rest = attribute(ev, w, [(a, b)])
            out.append((a, (b - a) * 1e-9,
                        {k: v * 1e-9 for k, v in sorted(
                            by.items(), key=lambda kv: -kv[1])}, rest * 1e-9))
    return sorted(out, key=lambda g: -g[1])


# -- per-step quantities ------------------------------------------------------
def host_gaps(w: Window) -> list[float]:
    """Device idle ns between consecutive decode-only steps: from the end of
    one's device work to the start of the next's."""
    out = []
    busy = w.busy
    for a, b in zip(w.steps, w.steps[1:]):
        if not (a.decode_only and b.decode_only and a.work and b.work):
            continue
        lo, hi = a.work[1], b.work[0]
        if hi <= lo:
            continue
        inside = busy[(busy[:, 1] > lo) & (busy[:, 0] < hi)]
        held = np.clip(inside, lo, hi)
        out.append(hi - lo - float(np.sum(held[:, 1] - held[:, 0])))
    return out


def plan_dispatch(w: Window) -> list[float]:
    """ns of the decode graph's ``repro.plan.run`` in each decode-only
    step."""
    return [st.plan_run[1] - st.plan_run[0] for st in w.steps
            if st.decode_only and st.plan_run]
