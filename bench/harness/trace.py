"""Device traces: taking one, and reducing it to busy time, operation time,
collective exposure and idle gaps.

The reduction works on plain event lists (``Events``), which ``load`` reads
from the profiler's ``.xplane.pb``.  A device's operations are the events on
its ``XLA Ops`` line; nested events (the body of a ``while``) count in the
union that makes busy time, and each event's self time (its duration less
its children's) makes operation time.  Host spans are the events of the host
threads: the benchmark's own ``bench.*`` annotations and the runtime's.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
from dataclasses import dataclass, field

COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|send|recv)")


@dataclass
class Events:
    """Device operations per device, and host spans, in ns on one clock."""
    device: dict = field(default_factory=dict)   # device -> [(name, t0, dur)]
    host: list = field(default_factory=list)     # [(name, t0, dur, thread)]

    @classmethod
    def from_json(cls, d: dict) -> "Events":
        return cls({k: [tuple(e) for e in v] for k, v in d["device"].items()},
                   [tuple(e) for e in d["host"]])


def start(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # the Python tracer would slow every call
    opts.host_tracer_level = 1        # keeps TraceAnnotation spans
    shutil.rmtree(log_dir, ignore_errors=True)
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop_and_load(log_dir: str) -> Events:
    import jax

    jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {log_dir}")
    ev = load(paths[0])
    shutil.rmtree(log_dir, ignore_errors=True)
    return ev


def load(path: str) -> Events:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = Events()
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out.device[plane.name] = [
                        (op_name(e.name), float(e.start_ns),
                         float(e.duration_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out.host.extend((e.name, float(e.start_ns),
                                 float(e.duration_ns), line.name)
                                for e in line.events if e.duration_ns > 0)
    return out


def op_name(text: str) -> str:
    """An operation's name out of the HLO text the TPU trace gives for it:
    ``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


# -- reduction ----------------------------------------------------------------
def op_kind(name: str) -> str:
    """An operation's name without its instance number: ``fusion.12`` ->
    ``fusion``."""
    return re.sub(r"(\.\d+)+$", "", name)


def union(intervals) -> list[tuple[float, float]]:
    """Disjoint, sorted (start, end) covering the given (start, end)."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def measure(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """Parts of disjoint sorted ``a`` not covered by disjoint sorted ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(ops) -> dict[str, float]:
    """Seconds of self time per operation kind, nesting resolved by
    containment on one line."""
    total: dict[str, float] = {}
    stack: list[list] = []      # [end, kind, duration, children's time]

    def close(entry):
        _, kind, dur, child = entry
        total[kind] = total.get(kind, 0.0) + max(dur - child, 0.0) * 1e-9

    for name, t0, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= t0:
            close(stack.pop())
        if stack:
            stack[-1][3] += dur
        stack.append([t0 + dur, op_kind(name), dur, 0.0])
    while stack:
        close(stack.pop())
    return total


@dataclass
class Summary:
    window_s: float
    busy_s: float                      # mean over devices
    op_s: dict                         # kind -> self seconds, summed over devices
    n_devices: int
    collective_exposed_s: float        # mean over devices
    idle_gaps: list                    # [(host span, seconds)], longest first

    def time_of(self, pattern: str) -> float:
        """Self seconds of every operation whose kind contains ``pattern``,
        summed over devices."""
        return sum(s for k, s in self.op_s.items() if pattern in k)


def reduce(ev: Events, lo: float, hi: float, *, n_gaps: int = 10) -> Summary:
    """Summary of the device work in the window [lo, hi] (ns)."""
    if not ev.device:
        raise ValueError("the trace holds no device operations")
    busy, exposed, ops = [], [], {}
    gaps_all = []
    for dev, evs in sorted(ev.device.items()):
        inside = [(n, t, d) for n, t, d in evs if t < hi and t + d > lo]
        u = clip(union((t, t + d) for _, t, d in inside), lo, hi)
        busy.append(measure(u))
        coll = clip(union((t, t + d) for n, t, d in inside
                          if COLLECTIVE.match(n)), lo, hi)
        comp = clip(union((t, t + d) for n, t, d in inside
                          if not COLLECTIVE.match(n)), lo, hi)
        exposed.append(measure(subtract(coll, comp)))
        for k, s in self_times(inside).items():
            ops[k] = ops.get(k, 0.0) + s
        if not gaps_all:            # idle gaps of the first device
            gaps_all = subtract([(lo, hi)], u)
    n = len(busy)
    gaps = sorted(gaps_all, key=lambda g: g[0] - g[1])[:n_gaps]
    return Summary(
        window_s=(hi - lo) * 1e-9, busy_s=sum(busy) / n * 1e-9, op_s=ops,
        n_devices=n, collective_exposed_s=sum(exposed) / n * 1e-9,
        idle_gaps=[(host_span_at(ev.host, (a + b) / 2), (b - a) * 1e-9)
                   for a, b in gaps])


def host_span_at(host, t: float) -> str:
    """What the host was doing at time ``t``: the innermost benchmark span
    (``bench.*``) that holds it, else the innermost host span of any kind,
    else ``idle``."""
    holding = [(not name.startswith("bench."), dur, name)
               for name, t0, dur, _ in host if t0 <= t < t0 + dur]
    return min(holding)[2] if holding else "idle"


def breakdown(s: Summary, n: int = 10) -> dict:
    ops = sorted(s.op_s.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in s.idle_gaps[:n]]}
