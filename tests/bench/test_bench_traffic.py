"""The work of a run is fixed by the traffic file: two seeds give the same
lengths, order and prefixes, and different token ids; no request stops
early."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench.harness import spec
from bench.harness.traffic import ClosedLoop, seed_words

REPO = Path(__file__).resolve().parents[2]


SERVING = [w["name"] for w in json.loads(
    (REPO / "BENCHMARK.json").read_text())["workloads"]
    if spec.load_cell(w["name"], REPO).traffic["kind"] == "serve_closed_loop"]
SEEDS = (7, 2**31 + 12345)


@pytest.mark.parametrize("cell", SERVING)
def test_two_seeds_same_schedule_different_ids(cell):
    c = spec.load_cell(cell, REPO)
    a, b = (ClosedLoop(c.traffic, s, c.config["vocab_size"]) for s in SEEDS)
    assert a.warm_plans() == b.warm_plans()
    firsts = set()
    for k in range(400):
        pa, pb = a.plan(k), b.plan(k)
        assert pa == pb
        ta, tb = a.prompt(pa), b.prompt(pb)
        assert len(ta) == len(tb) == a.prefix_len * (pa.prefix is not None) + pa.unique_len
        assert not np.array_equal(ta, tb)
        assert ta.min() >= 1 and ta.max() < c.config["vocab_size"]
        first = int(ta[a.prefix_len if pa.prefix is not None else 0])
        assert first not in firsts          # no accidental prefix match
        firsts.add(first)
        assert len(ta) + pa.output_len <= c.traffic["engine"]["max_len"]


@pytest.mark.parametrize("cell", SERVING)
def test_staggered_start(cell):
    c = spec.load_cell(cell, REPO)
    loop = ClosedLoop(c.traffic, 1, c.config["vocab_size"])
    top = max(loop.output_lens)
    outs = [loop.plan(i).output_len for i in range(loop.clients)]
    assert outs == [-(-(i + 1) * top // loop.clients) for i in range(loop.clients)]


def test_seed_words_take_large_seeds():
    assert seed_words(2**40 + 3, 2) != seed_words(2**40 + 4, 2)
    assert all(0 <= w < 2**31 for w in seed_words(2**33, 4))
    with pytest.raises(ValueError):
        seed_words(-1, 1)


@pytest.mark.parametrize("cell", ["toy.decode", "toy.prefix"])
def test_engine_work_same_for_every_seed(toy_root, cell):
    """The engine's steps, chunks, pages and output lengths repeat exactly
    under another seed, and every request emits all its planned tokens."""
    import jax

    import repro
    from bench.harness import serve, weights
    from repro.serve.engine import ServeConfig
    from repro.serve.paged import PagedConfig, PagedEngine

    c = spec.load_cell(cell, toy_root)
    e = c.traffic["engine"]
    seen = []
    for seed in SEEDS:
        params = weights.make(c.config, jax.random.key(seed_words(seed, 1)[0]))
        rt = repro.Runtime(n_workers=2)
        eng = PagedEngine(serve.model_config(c.config), params,
                          ServeConfig(max_batch=e["max_batch"], max_len=e["max_len"]),
                          paged=PagedConfig(page_size=e["page_size"], n_pages=e["n_pages"],
                                            prefill_chunk=e["prefill_chunk"]),
                          runtime=rt, max_executors=1)
        gen = ClosedLoop(c.traffic, seed, c.config["vocab_size"])
        rec = serve.ServeRecord(model=c.config, capacity=e["max_batch"])
        loop = serve.Loop(eng, gen, rec)
        for _ in range(gen.clients):
            loop.submit_next()
        for _ in range(120):
            loop.step()
        assert all(len(r.output) == gen.plan(r.request_id).output_len for r in loop.done)
        seen.append(([(s.chunks, s.decoded, tuple(s.ctx)) for s in rec.steps],
                     eng.stats(), [r.request_id for r in loop.done]))
        rt.close()
    assert seen[0] == seen[1]
    assert seen[0][1]["n_evictions"] == 0
