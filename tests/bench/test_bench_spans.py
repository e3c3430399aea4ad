"""The reduction of the program's own spans (``harness/spans.py``): on
hand-made events whose answers are known, on a few engine steps recorded on
a TPU v5e, and on the spans a toy-width engine writes on the CPU."""
import gzip
import importlib.util
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.harness import spans, trace

REPO = Path(__file__).resolve().parents[2]
RECORDED = REPO / "bench" / "data" / "danube3.decode_heavy.spans.trace.json.gz"
MS = 1e6      # ns
MAIN, EXEC = "python3", "graphi-exec-0"


def _reader(name):
    path = REPO / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


NEW_READERS = ["host_gap_ms_p50.decode_only", "readback_idle_share",
               "plan_dispatch_ms_p50.decode_only", "unattributed_idle_share"]


def _host(name, a, b, thread=MAIN):
    return (name, a * MS, (b - a) * MS, thread)


def hand_events(offset=7.0) -> trace.Events:
    """Two decode-only steps of 100 ms.  The device's clock reads ``offset``
    ms early: step 1's work runs at 22-90 ms on the host's clock, step 2's
    at 123-189, and a small operation of step 2's launch at 119-120."""
    host = [
        _host("bench.window", -10, 200),
        _host("repro.paged.step", 0, 100),
        _host("repro.paged.decode", 10, 30),
        _host("repro.plan.run", 12, 28),
        _host("repro.plan.node/a", 13, 15, EXEC),
        _host("repro.plan.node/b", 16, 20, EXEC),
        _host("repro.paged.sample", 30, 32),
        _host("repro.paged.readback", 32, 90),
        _host("repro.paged.emit", 90, 95),
        _host("repro.gc", 92, 94, EXEC),
        _host("repro.paged.step", 100, 200),
        _host("repro.paged.decode", 110, 130),
        _host("repro.plan.run", 112, 128),
        _host("repro.plan.node/a", 114, 116, EXEC),
        _host("repro.plan.node/b", 118, 122, EXEC),
        _host("repro.paged.readback", 132, 190),
        _host("PjitFunction(run)", 114, 115, EXEC),
    ]
    d = -offset
    dev = [("fusion.1", (22 + d) * MS, 30 * MS),
           ("while.2", (52.05 + d) * MS, (90 - 52.05) * MS),   # merges
           ("add.3", (119 + d) * MS, 1 * MS),
           ("while.2", (123 + d) * MS, 66 * MS)]
    return trace.Events(device={"/device:TPU:0": dev}, host=host)


def test_steps_pair_with_their_device_work():
    ev = hand_events()
    w = spans.analyse(ev)
    assert w.thread == MAIN and (w.lo, w.hi) == (-10 * MS, 200 * MS)
    a, b = w.steps
    assert a.decode_only and b.decode_only and a.chunks == 0
    assert a.plan_run == (12 * MS, 28 * MS)
    assert [n[0] for n in a.nodes] == [13 * MS, 16 * MS]
    assert a.readback == (32 * MS, 90 * MS)
    # device clock: step 1's work 15-83 (two operations merged), step 2's
    # 116-182; the small operation at 112-113 is a stretch of its own
    assert a.work == pytest.approx((15 * MS, 83 * MS))
    assert b.work == pytest.approx((116 * MS, 182 * MS))


def test_offset_from_the_causal_bounds():
    w = spans.analyse(hand_events(offset=7.0))
    # readback bounds 90-83 = 7 and 190-182 = 8; launch bound of step 2
    # (the step before it decode-only): 114 - 116 = -2
    assert w.clock.upper == pytest.approx(7 * MS)
    assert w.clock.offset == w.clock.upper
    assert w.clock.lower == pytest.approx(-2 * MS)
    w = spans.analyse(hand_events(offset=0.0))
    assert w.clock.offset == pytest.approx(0.0, abs=1e-3)


def test_a_readback_bound_below_the_launch_bound_is_left_out():
    """A third decode-only step whose readback returns before the end of
    the work it is paired with (here the work runs on past it) gives a
    bound that contradicts the launch bound; the offset ignores it."""
    ev = hand_events(offset=7.0)
    ev.host += [_host("repro.paged.step", 200, 300),
                _host("repro.paged.decode", 210, 230),
                _host("repro.plan.node/a", 214, 216, EXEC),
                _host("repro.paged.readback", 232, 280)]
    ev.device["/device:TPU:0"].append(("while.2", (223 - 7) * MS, 80 * MS))
    ev.host[0] = _host("bench.window", -10, 320)
    w = spans.analyse(ev)
    assert len(w.steps) == 3
    # readback bounds 7, 8 and 280 - (303 - 7) = -16; launch bound max(-2,
    # 214 - 216) = -2
    assert w.clock.lower == pytest.approx(-2 * MS)
    assert w.clock.offset == w.clock.upper == pytest.approx(7 * MS)


def test_idle_attributed_to_the_innermost_span():
    ev = hand_events()
    w = spans.analyse(ev)
    # the 0.05 ms between step 1's two operations is idle too
    assert spans.idle(w) == pytest.approx(
        [(-10 * MS, 22 * MS), (52 * MS, 52.05 * MS), (90 * MS, 119 * MS),
         (120 * MS, 123 * MS), (189 * MS, 200 * MS)])
    by, rest = spans.attribute(ev, w)
    want = {"repro.paged.step": 35, "repro.paged.decode": 4,
            "repro.plan.run": 20, "repro.paged.emit": 3, "repro.gc": 2,
            "repro.paged.readback": 1.05}
    assert by == pytest.approx({k: v * MS for k, v in want.items()})
    assert rest == pytest.approx(10 * MS)      # before the first step
    assert sum(by.values()) + rest == pytest.approx(
        sum(b - a for a, b in spans.idle(w)))
    g = spans.named_gaps(ev, w, 20 * MS)
    assert [x[1] for x in g] == pytest.approx([0.032, 0.029])
    # 90-119: emit 90-95 less the collection at 92-94, step 95-110,
    # decode 110-112, plan run 112-119
    assert g[1][2] == pytest.approx(
        {"repro.paged.step": 0.015, "repro.plan.run": 0.007,
         "repro.paged.emit": 0.003, "repro.paged.decode": 0.002,
         "repro.gc": 0.002})
    assert list(g[1][2])[:3] == ["repro.paged.step", "repro.plan.run",
                                 "repro.paged.emit"]


def test_per_step_quantities():
    w = spans.analyse(hand_events())
    # between the steps' work (83 to 116, device clock) the small
    # operation runs for 1 ms
    assert spans.host_gaps(w) == pytest.approx([32 * MS])
    assert spans.plan_dispatch(w) == pytest.approx([16 * MS, 16 * MS])


def test_a_step_with_a_chunk_is_not_decode_only():
    ev = hand_events()
    ev.host.append(_host("repro.paged.chunk", 111, 125, "paged-prefill"))
    w = spans.analyse(ev)
    assert w.steps[1].chunks == 1 and not w.steps[1].decode_only
    assert spans.host_gaps(w) == [] and len(spans.plan_dispatch(w)) == 1
    assert w.clock.upper == pytest.approx(7 * MS) and w.clock.lower is None


def test_innermost_of_nested_spans():
    segs = spans.innermost([(0, 10, "a"), (2, 4, "b"), (3, 4, "c"),
                            (6, 8, "d")])
    assert segs == [(0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 6, "a"),
                    (6, 8, "d"), (8, 10, "a")]


def test_readers_on_hand_events():
    run = SimpleNamespace(events=hand_events(), stats0={"n_compiles": 5},
                          stats1={"n_compiles": 5})
    got = {n: _reader(n)(run, None) for n in NEW_READERS}
    assert got["host_gap_ms_p50.decode_only"] == pytest.approx(32.0)
    assert got["plan_dispatch_ms_p50.decode_only"] == pytest.approx(16.0)
    assert got["readback_idle_share"] == pytest.approx(100 * 1.05 / 210)
    assert got["unattributed_idle_share"] == pytest.approx(100 * 10 / 210)
    assert _reader("compiles_in_window")(run, None) == 0
    run.stats1 = {"n_compiles": 7}
    assert _reader("compiles_in_window")(run, None) == 2


def test_readers_find_nothing_without_program_spans():
    """A program that writes no ``repro.*`` span (the parent of this
    benchmark's readers) gives no number, and no reader raises."""
    ev = hand_events()
    ev.host = [h for h in ev.host if not h[0].startswith("repro.")]
    run = SimpleNamespace(events=ev, stats0={}, stats1={})
    for n in NEW_READERS + ["compiles_in_window"]:
        assert _reader(n)(run, None) is None, n
    assert _reader(NEW_READERS[0])(SimpleNamespace(), None) is None


def test_device_busy_merges_close_operations():
    ev = trace.Events(device={"/device:TPU:0": [
        ("a", 0.0, 10.0), ("b", 5.0, 10.0), ("c", 20.0, 5.0),
        ("d", 100.0, 1.0)]})
    assert spans.device_busy(ev).tolist() == [[0, 15], [20, 25], [100, 101]]
    assert spans.device_busy(ev, 10.0).tolist() == [[0, 25], [100, 101]]
    assert spans.device_busy(trace.Events()).shape == (0, 2)


def test_steps_of_a_toy_engine_on_the_cpu(tmp_path):
    """The spans the program writes pair up as ``spans.steps`` expects: one
    step per ``engine.step()``, its chunks and decode as the engine counted
    them, and a decode-only step's plan run, node calls and readback."""
    import jax
    from jax.profiler import TraceAnnotation

    import repro
    from repro.configs.base import get_config
    from repro.models import transformer
    from repro.serve.engine import Request, ServeConfig
    from repro.serve.paged import PagedConfig, PagedEngine

    cfg = get_config("gemma-2b", smoke=True).reduced(vocab_size=260)
    params = transformer.init_params(cfg, jax.random.key(3))
    rt = repro.Runtime(n_workers=2)
    try:
        eng = PagedEngine(cfg, params, ServeConfig(max_batch=2, max_len=64),
                          paged=PagedConfig(page_size=8, prefill_chunk=8),
                          runtime=rt, decode_host_mode="static")
        truth = []
        trace.start(str(tmp_path / "trace"))
        with TraceAnnotation("bench.window"):
            eng.submit(Request(request_id=0, prompt=list(range(1, 21)),
                               max_new_tokens=8))
            for k in range(12):
                if k == 4:
                    eng.submit(Request(request_id=1, prompt=[7] * 17,
                                       max_new_tokens=2))
                a = eng.stats()
                eng.step()
                b = eng.stats()
                truth.append((b["n_chunks"] - a["n_chunks"],
                              b["n_decode_steps"] > a["n_decode_steps"]))
        ev = trace.stop_and_load(str(tmp_path / "trace"))
    finally:
        rt.close()
    w = spans.analyse(ev)
    assert w is not None and not ev.device
    assert [(s.chunks, s.decode is not None) for s in w.steps] == truth
    only = [s for s in w.steps if s.decode_only]
    assert only and len(only) < len(w.steps)
    for s in only:
        assert s.plan_run and s.nodes and s.readback
        assert s.decode[0] <= s.plan_run[0] <= s.plan_run[1] <= s.decode[1]
        assert s.readback[0] >= s.decode[1]
    assert len(spans.plan_dispatch(w)) == len(only)
    # no device plane on the CPU: nothing to pair, no offset
    assert w.clock.offset is None and spans.host_gaps(w) == []


@pytest.fixture(scope="module")
def recorded():
    """The recorded steps, with the window narrowed to them: the recording
    keeps only their events."""
    with gzip.open(RECORDED, "rt") as f:
        rec = json.load(f)
    ev = trace.Events.from_json(rec["events"])
    lo, hi = rec["window_ns"]
    ev.host = [h for h in ev.host if h[0] != "bench.window"]
    ev.host.append(("bench.window", lo, hi - lo, spans.main_thread(ev)))
    return rec, ev


def test_recorded_steps_and_clock(recorded):
    rec, ev = recorded
    w = spans.analyse(ev)
    assert [(s.chunks, s.decode is not None) for s in w.steps] == [
        (st["chunks"], st["decoded"]) for st in rec["steps"]]
    pairs = [(a, b) for a, b in zip(w.steps, w.steps[1:])
             if a.decode_only and b.decode_only]
    assert pairs
    # as recorded, a step's device work starts before the first node call
    # of its decode could have launched it: the clocks are offset
    assert any(b.work[0] < min(n[0] for n in b.nodes) for _, b in pairs)
    off = w.clock.offset
    assert w.clock.lower <= off == w.clock.upper
    # corrected, no decode-only step's work starts before its launching
    # span, and none ends after its readback
    for _, b in pairs:
        assert b.work[0] + off >= min(n[0] for n in b.nodes)
    for st in w.steps:
        if st.decode_only:
            assert st.work[1] + off <= st.readback[1]


def test_recorded_readers_give_numbers(recorded):
    _, ev = recorded
    run = SimpleNamespace(events=ev, stats0={}, stats1={})
    got = {n: _reader(n)(run, None) for n in NEW_READERS}
    assert 1.0 < got["host_gap_ms_p50.decode_only"] < 6.0, got
    assert 1.0 < got["plan_dispatch_ms_p50.decode_only"] < 4.0, got
    assert 0.0 <= got["readback_idle_share"] < 100.0, got
    assert 0.0 <= got["unattributed_idle_share"] < 100.0, got
    w = spans.analyse(ev)
    by, rest = spans.attribute(ev, w)
    idle = sum(b - a for a, b in spans.idle(w))
    assert sum(by.values()) + rest == pytest.approx(idle)
