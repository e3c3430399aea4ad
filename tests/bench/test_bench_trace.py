"""The reduction from a device trace to busy time, operation time,
collective exposure and idle gaps: on hand-made events whose answers are
known, and on a few engine steps recorded on a TPU v5e."""
import gzip
import json
from pathlib import Path

import pytest

from bench.harness import flops, trace

REPO = Path(__file__).resolve().parents[2]


RECORDED = REPO / "bench" / "data" / "danube3.decode_heavy.trace.json.gz"


def test_union_subtract_measure():
    u = trace.union([(0, 2), (1, 3), (5, 6)])
    assert u == [(0, 3), (5, 6)]
    assert trace.measure(u) == 4
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert trace.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]


def test_self_times_resolve_nesting():
    ops = [("while.1", 0, 100), ("fusion.3", 10, 20), ("kernel", 40, 30),
           ("fusion.4", 150, 10)]
    t = trace.self_times(ops)
    assert t["while"] == pytest.approx(50e-9)
    assert t["fusion"] == pytest.approx(30e-9)
    assert t["kernel"] == pytest.approx(30e-9)


def test_reduce_by_hand():
    ev = trace.Events(
        device={"/device:TPU:0": [("fusion.1", 0, 40), ("all-gather.2", 30, 30),
                                  ("fusion.5", 80, 10)],
                "/device:TPU:1": [("fusion.1", 0, 50), ("all-reduce.1", 50, 20)]},
        host=[("bench.step", 0, 100, "python"), ("bench.submit", 62, 10, "python")])
    s = trace.reduce(ev, 0, 100)
    assert s.window_s == pytest.approx(100e-9)
    # device 0 busy 0-60 and 80-90 = 70; device 1 busy 0-70 = 70
    assert s.busy_s == pytest.approx(70e-9)
    # exposed collective: device 0 40-60 = 20; device 1 50-70 = 20
    assert s.collective_exposed_s == pytest.approx(20e-9)
    assert s.n_devices == 2
    # idle gaps of device 0: 60-80 (host in bench.submit) and 90-100
    assert s.idle_gaps[0][0] == "bench.submit"
    assert s.idle_gaps[0][1] == pytest.approx(20e-9)
    assert trace.breakdown(s)["device_ops"][0][0] == "fusion"


def test_recorded_trace():
    with gzip.open(RECORDED, "rt") as f:
        rec = json.load(f)
    ev = trace.Events.from_json(rec["events"])
    lo, hi = rec["window_ns"]
    s = trace.reduce(ev, lo, hi)
    assert 0 < s.busy_s <= s.window_s
    kernel = s.time_of("paged_decode_attention")
    assert kernel > 0
    # the kernel's self time is the sum of its events (it nests nothing)
    dev = sorted(ev.device)[0]
    events = [d for n, t, d in ev.device[dev] if "paged_decode_attention" in n
              and lo <= t and t + d <= hi]
    assert kernel == pytest.approx(sum(events) * 1e-9, rel=0.05)
    # roofline share of the recorded decode steps: below 100 %
    m = json.loads((REPO / "bench/configs/danube3.json").read_text())
    least = sum(m["n_layers"] * flops.least_time(
        *flops.paged_decode_attention_cost(m, st["ctx"]), 197e12, 819e9)
        for st in rec["steps"] if st["decoded"])
    assert 0 < least / kernel < 1.0
