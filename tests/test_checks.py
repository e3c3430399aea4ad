"""``repro.checks`` test suite (ISSUE 7): every rule must fire on a seeded
defect and stay silent on a healthy artifact.

Structure mirrors the subsystem: Report currency, G-*/S-*/P-* structural
invariants (defects injected through the graph's private dicts or
``dataclasses.replace`` on frozen plans), E-FIFO over synthetic segment
journals, effect inference (scan-body scatters, annotations, opaque
fallback), hazard analysis (unordered scatter pairs, executor-placement
downgrade), the real paged decode × prefill-chunk cross-graph
certification, the W-ASSERT source scan, and the ``check=`` /
``Executable.verify()`` API integration.
"""
import copy
import dataclasses

import jax
import jax.numpy as jnp
import pytest

import repro
from repro.checks import (
    Report,
    check_graph,
    check_hazards,
    check_plan,
    check_schedule,
    check_segment_fifo,
    cross_graph_hazards,
    infer_effects,
    scan_asserts,
    segment_queues,
    shared_buffers,
    verify_all,
)
from repro.core import KNL7250, Graph, GraphValidationError, make_schedule
from repro.core.scheduler import Schedule
from repro.core.static_host import compile_host_plan, layered_graph
from repro.models import transformer
from repro.serve.step import make_paged_decode_step, make_prefill_chunk_step
from test_capture import TINY


def _setup(L=3, W=2, n_exec=2):
    g = layered_graph(L=L, W=W)
    sched = make_schedule(g, KNL7250, n_executors=n_exec, team_size=1)
    return g, sched, compile_host_plan(g, sched)


def _rules(rep):
    return set(rep.by_rule())


# ---------------------------------------------------------------------------
# Report currency
# ---------------------------------------------------------------------------

def test_report_currency():
    rep = Report()
    assert rep.ok and rep.summary().startswith("0 error")
    rep.add("X-A", "warning", "w msg")
    rep.add("X-B", "error", "e msg", node="n1")
    assert not rep.ok and len(rep.errors) == 1 and len(rep.warnings) == 1
    # render sorts most-severe first and includes rule ids
    body = rep.render()
    assert body.index("X-B") < body.index("X-A")
    with pytest.raises(GraphValidationError, match="X-B"):
        rep.raise_if_errors()
    with pytest.raises(ValueError):
        rep.add("X-C", "fatal", "not a severity")


def test_report_scoped_and_extend():
    inner = Report()
    inner.add("X-A", "info", "msg")
    outer = Report()
    outer.extend(inner.scoped("zone"))
    assert outer.findings[0].where == "zone"
    assert Report().render() == "clean: no findings"


# ---------------------------------------------------------------------------
# G-* graph invariants
# ---------------------------------------------------------------------------

def test_graph_clean():
    g, _, _ = _setup()
    assert check_graph(g).ok


def test_graph_cycle_flagged():
    g, _, _ = _setup()
    # Graph.add refuses forward deps, so a cycle can only enter through the
    # private dicts — exactly the tampered artifact the checker exists for
    name = g.names[1]
    node = g[name]
    g._nodes[name] = dataclasses.replace(node, deps=node.deps + (g.names[-1],))
    g._version += 1
    rep = check_graph(g)
    assert "G-CYCLE" in _rules(rep) and not rep.ok


def test_graph_self_and_unknown_dep():
    g, _, _ = _setup()
    name = g.names[2]
    g._nodes[name] = dataclasses.replace(g[name], deps=(name, "ghost"))
    g._version += 1
    rep = check_graph(g)
    msgs = [f.message for f in rep.errors if f.rule_id == "G-DEP"]
    assert any("itself" in m for m in msgs)
    assert any("ghost" in m for m in msgs)


# ---------------------------------------------------------------------------
# S-* schedule invariants
# ---------------------------------------------------------------------------

def test_schedule_clean():
    g, sched, _ = _setup()
    assert check_schedule(sched, g).ok


def test_schedule_dep_order_violation():
    g, sched, plan = _setup()
    pl = dict(sched.placements)
    late = plan.names[plan.programs[0][-1]]     # an op with executed deps
    e, _, _ = pl[late]
    pl[late] = (e, -1.0, -0.5)                  # starts before its deps end
    rep = check_schedule(dataclasses.replace(sched, placements=pl), g)
    assert "S-DEP" in _rules(rep)


def test_schedule_executor_out_of_range():
    g, sched, _ = _setup()
    pl = dict(sched.placements)
    n = next(iter(pl))
    _, s, t = pl[n]
    pl[n] = (99, s, t)
    rep = check_schedule(dataclasses.replace(sched, placements=pl), g)
    assert "S-EXEC" in _rules(rep)


def test_schedule_overlap():
    g, sched, _ = _setup()
    pl = dict(sched.placements)
    a, b = [k for k in pl if pl[k][2] > pl[k][1]][:2]
    pl[b] = pl[a]                               # same executor, same interval
    rep = check_schedule(dataclasses.replace(sched, placements=pl), g)
    assert "S-OVERLAP" in _rules(rep)


def test_schedule_coverage():
    g, sched, _ = _setup()
    pl = dict(sched.placements)
    pl.pop(next(iter(pl)))
    pl["phantom"] = (0, 0.0, 0.0)
    rep = check_schedule(dataclasses.replace(sched, placements=pl), g)
    msgs = [f.message for f in rep.errors if f.rule_id == "S-COVER"]
    assert any("missing" in m for m in msgs)
    assert any("not in graph" in m for m in msgs)


# ---------------------------------------------------------------------------
# P-* plan invariants
# ---------------------------------------------------------------------------

def test_plan_clean_and_verify_all():
    g, sched, plan = _setup()
    assert check_plan(plan, g).ok
    assert verify_all(g, sched, plan).ok


def test_plan_dropped_counter_deadlocks():
    g, _, plan = _setup()
    i = plan.programs[-1][-1]                   # an op that waits on deps
    n_wait = tuple(w + (1 if k == i else 0)
                   for k, w in enumerate(plan.n_wait))
    rep = check_plan(dataclasses.replace(plan, n_wait=n_wait), g)
    assert {"P-COUNTER", "P-REACH"} <= _rules(rep)
    assert any("deadlock" in f.message for f in rep.errors)


def test_plan_low_counter_races():
    g, _, plan = _setup()
    i = next(k for k in plan.programs[-1] if plan.n_wait[k] > 0)
    n_wait = tuple(w - (1 if k == i else 0)
                   for k, w in enumerate(plan.n_wait))
    rep = check_plan(dataclasses.replace(plan, n_wait=n_wait), g)
    assert "P-COUNTER" in _rules(rep)
    assert any("before its inputs exist" in f.message for f in rep.errors)


def test_plan_dropped_seed():
    g, _, plan = _setup()
    e = next(i for i, s in enumerate(plan.seeds) if s)
    seeds = tuple(s[1:] if i == e else s for i, s in enumerate(plan.seeds))
    rep = check_plan(dataclasses.replace(plan, seeds=seeds), g)
    assert {"P-SEED", "P-REACH"} <= _rules(rep)


def test_plan_program_order_violation():
    g, _, plan = _setup()
    progs = tuple(tuple(reversed(p)) for p in plan.programs)
    rep = check_plan(dataclasses.replace(plan, programs=progs), g)
    assert "P-TOPO" in _rules(rep)


def test_plan_owner_corruption():
    g, _, plan = _setup()
    owner = list(plan.owner)
    owner[plan.programs[0][0]] = 99
    rep = check_plan(dataclasses.replace(plan, owner=owner), g)
    assert {"P-COVER", "P-POISON"} <= _rules(rep)


def test_plan_stale_after_graph_mutation():
    g, _, plan = _setup()
    g.add_op("extra", deps=("out",), fn=lambda v: v)
    rep = check_plan(plan, g)
    assert _rules(rep) == {"P-STALE"}
    # the runtime enforces the same staleness contract at replay time
    with pytest.raises(GraphValidationError, match="mutated"):
        plan.run({"x": 1.0})


# ---------------------------------------------------------------------------
# E-FIFO segment journal
# ---------------------------------------------------------------------------

def test_fifo_cross_order_deadlock():
    rep = check_segment_fifo({0: [1, 2], 1: [2, 1]})
    assert "E-FIFO" in {f.rule_id for f in rep.errors}
    assert any("opposite orders" in f.message for f in rep.errors)


def test_fifo_duplicate_batch():
    rep = check_segment_fifo({0: [1, 1]})
    assert any("twice" in f.message for f in rep.errors)


def test_fifo_consistent_is_info_only():
    log = [(0, 1, "s0"), (1, 1, "s1"), (0, 2, "s2"), (1, 2, "s3")]
    rep = check_segment_fifo(segment_queues(log))
    assert rep.ok
    assert any(f.severity == "info" for f in rep.findings)


# ---------------------------------------------------------------------------
# effect inference
# ---------------------------------------------------------------------------

def test_effects_scan_body_scatter_seen():
    # the paged decode shape: a scatter hidden inside a lax.scan body must
    # still mark the pool input as written
    def fn(pool, xs):
        def body(p, x):
            p = p.at[0].set(x)
            return p, x * 2.0
        pool, ys = jax.lax.scan(body, pool, xs)
        return pool.sum() + ys.sum()

    pool = jnp.zeros((4, 8), jnp.float32)
    xs = jnp.ones((4, 8), jnp.float32)
    exe = repro.compile(fn, pool, xs)
    eff = infer_effects(exe.graph)
    bind = exe.captured.bind((pool, xs))
    pool_buf = next(n for n, v in bind.items() if v is pool)
    assert pool_buf in eff.written()
    assert eff.writers(pool_buf)


def test_effects_annotated_and_opaque():
    g = Graph("hand")
    g.add_op("buf", kind="input")
    g.add_op("w", deps=("buf",), fn=lambda b: b,
             meta={"effects": {"reads": ["buf"], "writes": ["buf"],
                               "carries": ["buf"]}})
    g.add_op("r", deps=("w",), fn=lambda b: b)      # no meta: opaque reader
    eff = infer_effects(g)
    assert eff.effects["w"].source == "annotated"
    assert eff.effects["w"].writes == {"buf"}
    assert eff.effects["r"].source == "opaque"
    assert eff.effects["r"].reads == {"buf"}        # carried through 'w'
    assert eff.read_only(["buf"]) is False


def test_shared_buffers_by_identity():
    x = jnp.zeros((2,))
    y = jnp.ones((3,))
    pairs = shared_buffers({"a": x, "b": y, "k": 3},
                           {"c": x, "d": jnp.zeros((2,)), "k2": 3})
    assert pairs == [("a", "c")]


# ---------------------------------------------------------------------------
# hazard analysis
# ---------------------------------------------------------------------------

def _two_writer_graph():
    g = Graph("haz")
    g.add_op("buf", kind="input")
    ann = {"effects": {"reads": ["buf"], "writes": ["buf"],
                       "carries": ["buf"]}}
    g.add_op("w1", deps=("buf",), fn=lambda b: b, meta=dict(ann))
    g.add_op("w2", deps=("buf",), fn=lambda b: b, meta=dict(ann))
    return g


def test_unordered_scatter_pair_flagged():
    rep = check_hazards(_two_writer_graph())
    assert any(f.rule_id == "H-WW" and f.severity == "error"
               for f in rep.findings)


def test_dep_ordered_writers_clean():
    g = Graph("haz-ok")
    g.add_op("buf", kind="input")
    ann = {"effects": {"reads": ["buf"], "writes": ["buf"],
                       "carries": ["buf"]}}
    g.add_op("w1", deps=("buf",), fn=lambda b: b, meta=dict(ann))
    g.add_op("w2", deps=("w1",), fn=lambda b: b, meta=dict(ann))
    assert check_hazards(g).ok


def test_placement_serialization_downgrades_to_warning():
    g = _two_writer_graph()
    sched = Schedule(
        graph_name=g.name, policy="manual", n_executors=1, team_size=1,
        makespan=2.0,
        placements={"buf": (0, 0.0, 0.0), "w1": (0, 0.0, 1.0),
                    "w2": (0, 1.0, 2.0)},
    )
    rep = check_hazards(g, schedule=sched)
    ww = [f for f in rep.findings if f.rule_id == "H-WW"]
    assert ww and all(f.severity == "warning" for f in ww)
    assert any("executor placement" in f.message for f in ww)


def test_cross_graph_write_write_error():
    g1, g2 = _two_writer_graph(), _two_writer_graph()
    rep = cross_graph_hazards(infer_effects(g1), infer_effects(g2),
                              [("buf", "buf")])
    assert any(f.rule_id == "H-XWW" for f in rep.errors)


# ---------------------------------------------------------------------------
# paged decode × prefill chunk: both read the one pool, neither writes it
# ---------------------------------------------------------------------------

def test_paged_pair_has_zero_write_conflicts():
    cfg = TINY["transformer"]
    assert transformer.paged_supported(cfg)
    params = transformer.init_params(cfg, jax.random.key(0))
    B, max_len, page = 2, 32, 8
    n_pt = max_len // page
    pcache = transformer.init_paged_cache(cfg, B, max_len,
                                          n_pages=B * n_pt, page_size=page)
    pages = pcache["pages"]         # ONE pool object bound by both graphs
    cache_spec = {"len": jnp.zeros((B,), jnp.int32),
                  "table": jnp.full((B, n_pt), -1, jnp.int32),
                  "pages": pages}
    tok = jnp.zeros((B, 1), jnp.int32)
    dec = repro.compile(make_paged_decode_step(cfg), params,
                        cache_spec, tok, name="chk.paged_decode")
    row = jnp.full((n_pt,), -1, jnp.int32)
    batch = {"tokens": jnp.zeros((1, page), jnp.int32)}
    start, valid = jnp.int32(0), jnp.int32(page)
    chunk = repro.compile(make_prefill_chunk_step(cfg, page), params, pages,
                          row, batch, start, valid, name="chk.prefill_chunk")

    eff_d = infer_effects(dec.graph)
    eff_c = infer_effects(chunk.graph)
    bind_d = dec.captured.bind((params, cache_spec, tok))
    bind_c = chunk.captured.bind((params, pages, row, batch, start, valid))
    shared = shared_buffers(bind_d, bind_c)
    pool_ids = {id(x) for x in jax.tree.leaves(pages)}
    pool_shared = [(a, b) for a, b in shared if id(bind_d[a]) in pool_ids]

    assert pool_shared, "alias discovery found no shared pool buffers"
    # the decode step returns its K/V for the engine to install: it is
    # certified read-only over the pool, and so is the chunk graph
    assert eff_d.read_only(a for a, _ in pool_shared)
    assert eff_c.read_only(b for _, b in pool_shared)
    # ... so the pair has zero unordered write/write conflicts
    rep = cross_graph_hazards(eff_d, eff_c, shared)
    assert not any(f.rule_id == "H-XWW" for f in rep.findings)
    assert rep.ok


@pytest.mark.parametrize("writes", [False, True], ids=["read_only", "writes_pool"])
def test_zoo_paged_rule_requires_both_graphs_read_only(monkeypatch, writes):
    """``python -m repro.checks --zoo``'s paged rule certifies the decode
    step and the prefill chunk read-only over the pool (C-RO for each), and
    reports an error for a decode step that writes the pool."""
    import repro.serve.step as serve_step
    from repro.checks.cli import run_zoo_arch

    if writes:
        real = serve_step.make_paged_decode_step

        def writing(cfg):
            step = real(cfg)

            def paged_decode_step(params, cache, tokens):
                logits, kv = step(params, cache, tokens)
                pool = cache["pages"]
                return logits, {n: pool[n].at[0, :, 0, :, :kv[n].shape[-1]]
                                .set(kv[n][:, 0]) for n in ("k", "v")}

            return paged_decode_step

        monkeypatch.setattr(serve_step, "make_paged_decode_step", writing)
    rep = run_zoo_arch("h2o-danube3-4b")
    paged = [f for f in rep.findings if f.where.endswith("/paged")]
    certified = {f.message.split(" certified")[0] for f in paged
                 if f.rule_id == "C-RO"}
    errors = [f.message for f in paged if f.severity == "error"]
    if writes:
        assert certified == {"prefill chunk"}
        assert any("paged decode writes a pool buffer" in m for m in errors)
    else:
        assert certified == {"paged decode", "prefill chunk"}
        assert not errors and rep.ok


# ---------------------------------------------------------------------------
# W-ASSERT source rule
# ---------------------------------------------------------------------------

def test_assertscan_library_tree_clean():
    rep = scan_asserts()
    assert rep.ok, rep.render()


def test_assertscan_flags_bare_assert(tmp_path):
    (tmp_path / "mod.py").write_text("def f(x):\n    assert x > 0\n    return x\n")
    rep = scan_asserts(tmp_path)
    hits = [f for f in rep.errors if f.rule_id == "W-ASSERT"]
    assert hits and "python -O" in hits[0].message


# ---------------------------------------------------------------------------
# API integration: check=, strict builds, Executable.verify()
# ---------------------------------------------------------------------------

def test_compile_rejects_unknown_check_mode():
    with pytest.raises(ValueError, match="check"):
        repro.compile(layered_graph(2, 2), n_workers=2, n_executors=2,
                      team_size=1, check="bogus")


def test_compile_basic_rejects_tampered_graph():
    g = layered_graph(2, 2)
    name = g.names[1]
    g._nodes[name] = dataclasses.replace(g[name], deps=(g.names[-1],))
    g._version += 1
    with pytest.raises(GraphValidationError, match="G-CYCLE"):
        repro.compile(g, n_workers=2, n_executors=2, team_size=1)
    # check="off" defers to the (later) scheduling failure instead
    exe = repro.compile(g, n_workers=2, n_executors=2, team_size=1,
                        check="off")
    assert exe.check == "off"


def test_strict_build_and_verify():
    g = layered_graph(3, 2)
    exe = repro.compile(g, n_workers=2, n_executors=2, team_size=1,
                        check="strict")
    plan = exe.host_plan(2)                     # strict-verified build
    assert plan.n_ops == len(g) - 1
    rep = exe.verify()
    assert rep.ok, rep.render()
    res = plan.run({"x": 1.0})
    assert res.outputs == copy.deepcopy(g).execute({"x": 1.0})
