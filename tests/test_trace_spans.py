"""The program's spans on the profiler's clock (``repro.*``, ``core/trace.py``):
a toy-width ``PagedEngine`` driven under ``jax.profiler`` on the CPU writes
each span on the expected thread with the expected nesting; the compile
count, the ``repro.gc`` span and the Runtime's lease-wait counters."""
import gc
import glob
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData, TraceAnnotation

import repro
from repro.configs.base import get_config
from repro.core import trace
from repro.models import transformer
from repro.serve.engine import Request, ServeConfig
from repro.serve.paged import PagedConfig, PagedEngine

MAIN_CHILDREN = ("repro.paged.admit", "repro.paged.alloc", "repro.paged.lease",
                 "repro.paged.upload", "repro.paged.decode",
                 "repro.paged.sample", "repro.paged.readback",
                 "repro.paged.emit", "repro.paged.chunk_join",
                 "repro.paged.insert_chunk")


def _profile_start(log_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def _profile_stop(log_dir) -> list:
    """Host spans as (name, start, end, line); one line per thread."""
    jax.profiler.stop_trace()
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                out.extend((e.name, e.start_ns, e.end_ns, (k, line.name))
                           for e in line.events)
    return out


def _inside(child, parent) -> bool:
    return (child[3] == parent[3] and parent[1] <= child[1]
            and child[2] <= parent[2])


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Two requests: the second shares the first's first page and part of
    its second (copy on write), and prefills in chunks while the first
    decodes (overlapped steps) after a step of chunks alone."""
    cfg = get_config("gemma-2b", smoke=True).reduced(vocab_size=260)
    params = transformer.init_params(cfg, jax.random.key(3))
    rt = repro.Runtime(n_workers=2)
    eng = PagedEngine(cfg, params, ServeConfig(max_batch=2, max_len=64),
                      paged=PagedConfig(page_size=8, prefill_chunk=8),
                      runtime=rt)
    first = list(range(1, 21))
    log_dir = tmp_path_factory.mktemp("profile")
    s0 = eng.stats()
    _profile_start(log_dir)
    eng.submit(Request(request_id=0, prompt=first, max_new_tokens=12))
    while not eng.slots[0]:
        eng.step()
    eng.submit(Request(request_id=1, prompt=first[:12] + list(range(40, 58)),
                       max_new_tokens=3))
    eng.run()
    with TraceAnnotation("test.collect"):
        gc.collect()
    events = _profile_stop(log_dir)
    s1 = eng.stats()
    yield events, s0, s1, eng
    rt.close()


def test_spans_on_their_threads_and_nested(served):
    ev, s0, s1, _ = served
    steps = [e for e in ev if e[0] == "repro.paged.step"]
    assert len(steps) == s1["n_steps"] - s0["n_steps"]
    main = {e[3] for e in steps}
    assert len(main) == 1
    for name in MAIN_CHILDREN:
        got = [e for e in ev if e[0] == name]
        assert got, name
        for e in got:   # each on the main thread, inside one step
            assert any(_inside(e, s) for s in steps), name
    admits = [e for e in ev if e[0] == "repro.paged.admit"]
    cow = [e for e in ev if e[0] == "repro.paged.cow_copy"]
    assert len(cow) == s1["n_cow_copies"] - s0["n_cow_copies"] == 1
    assert any(_inside(cow[0], a) for a in admits)
    # every host-plan run: the decode's on the main thread, a chunk's in its
    # chunk span (the prefill thread, or the main one in a step of chunks
    # alone); every node call on an executor thread
    chunks = [e for e in ev if e[0] == "repro.paged.chunk"]
    decodes = [e for e in ev if e[0] == "repro.paged.decode"]
    for run in (e for e in ev if e[0] == "repro.plan.run"):
        assert any(_inside(run, p) for p in chunks + decodes)
    assert {e[3][1] for e in chunks} == {"paged-prefill", next(iter(main))[1]}
    nodes = [e for e in ev if e[0].startswith("repro.plan.node/")]
    assert nodes and all(e[3][1].startswith("graphi-exec-") for e in nodes)


def test_chunk_spans_count_the_chunks(served):
    ev, s0, s1, _ = served
    n = sum(1 for e in ev if e[0] == "repro.paged.chunk")
    assert n == s1["n_chunks"] - s0["n_chunks"] > 0
    assert s1["n_overlapped_chunks"] > s0["n_overlapped_chunks"]


def test_gc_span_brackets_a_collection(served):
    ev = served[0]
    outer, = [e for e in ev if e[0] == "test.collect"]
    assert any(_inside(e, outer) for e in ev if e[0] == trace.GC_SPAN)


def test_compile_count_sees_a_recompile():
    pt = trace.install()
    assert trace.install() is pt
    x5, x6 = jax.block_until_ready((jnp.ones(5), jnp.ones(6)))
    f = jax.jit(lambda x: x * 3 + 1)
    n0 = pt.n_compiles
    f(x5).block_until_ready()
    assert pt.n_compiles == n0 + 1
    f(x5).block_until_ready()               # cached: no compile
    assert pt.n_compiles == n0 + 1
    f(x6).block_until_ready()               # a new shape forces one
    assert pt.n_compiles == n0 + 2


def test_engine_stats_count_a_forced_recompile(served):
    eng = served[3]
    eng.submit(Request(request_id=2, prompt=[5, 6, 7], max_new_tokens=2))
    eng.run()
    n0 = eng.stats()["n_compiles"]
    eng.submit(Request(request_id=3, prompt=[5, 6, 7], max_new_tokens=2))
    eng.run()
    assert eng.stats()["n_compiles"] == n0       # warm: nothing compiles
    jax.clear_caches()
    eng.submit(Request(request_id=4, prompt=[5, 6, 7], max_new_tokens=2))
    eng.run()
    assert eng.stats()["n_compiles"] > n0


def test_health_counts_a_blocked_lease(tmp_path):
    rt = repro.Runtime(n_workers=2)
    try:
        held = rt.lease(2)
        assert rt.health()["n_lease_waits"] == 0
        got = []
        th = threading.Thread(target=lambda: got.append(rt.lease(1)))
        _profile_start(tmp_path)
        th.start()
        time.sleep(0.05)
        held.release()
        th.join(timeout=10)
        ev = _profile_stop(tmp_path)
        assert not th.is_alive() and got
        got[0].release()
        h = rt.health()
        assert h["n_lease_waits"] == 1 and h["lease_wait_s"] >= 0.04
        waits = [e for e in ev if e[0] == "repro.runtime.lease_wait"]
        assert len(waits) == 1 and waits[0][2] - waits[0][1] >= 4e7
        rt.lease(2).release()               # free: no wait counted
        assert rt.health()["n_lease_waits"] == 1
    finally:
        rt.close()


@pytest.mark.skipif(not sys.platform.startswith("linux"),
                    reason="OS thread names are set on Linux only")
def test_name_thread_names_the_profiler_line(tmp_path):
    def work():
        trace.name_thread("a-long-thread-name")
        with TraceAnnotation("test.named"):
            pass

    _profile_start(tmp_path)
    th = threading.Thread(target=work)
    th.start()
    th.join(timeout=10)
    ev = _profile_stop(tmp_path)
    (line,) = {e[3][1] for e in ev if e[0] == "test.named"}
    assert line == "a-long-thread-n"        # Linux keeps 15 bytes
