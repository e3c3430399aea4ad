"""Paged-KV serving engine: bit-exact parity with unbatched greedy decode
and the per-slot ContinuousEngine, prefix sharing with copy-on-write,
eviction/recompute under memory pressure, and chunked-prefill admission."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import transformer
from repro.serve.engine import ContinuousEngine, Request, ServeConfig
from repro.serve.paged import PagedConfig, PagedEngine, PagePool
from repro.serve.step import mask_pad_vocab


@pytest.fixture(scope="module")
def model():
    # padded_vocab (512) > vocab_size (260): the pad-mask is load-bearing
    cfg = get_config("gemma-2b", smoke=True).reduced(vocab_size=260)
    params = transformer.init_params(cfg, jax.random.key(3))
    return cfg, params


@pytest.fixture(scope="module")
def engine(model):
    cfg, params = model
    eng = PagedEngine(cfg, params, ServeConfig(max_batch=2, max_len=48),
                      paged=PagedConfig(page_size=8, prefill_chunk=8))
    yield eng
    eng.close()


def _reference_decode(cfg, params, prompt, n_new):
    """Unbatched greedy reference (pad-masked argmax)."""
    cache = transformer.init_cache(cfg, 1, len(prompt) + n_new + 1)
    logits, cache = transformer.prefill(
        cfg, params, {"tokens": jnp.asarray(prompt)[None]}, cache)
    out = []
    for _ in range(n_new):
        t = int(jnp.argmax(mask_pad_vocab(logits, cfg.vocab_size), -1)[0])
        out.append(t)
        logits, cache = transformer.decode_step(
            cfg, params, jnp.asarray([[t]], jnp.int32), cache)
    return out


# ---------------------------------------------------------------------------
# PagePool: pure host-side allocator semantics
# ---------------------------------------------------------------------------

def test_page_pool_refcounts_and_cold_reclaim():
    from repro.serve.paged import PoolExhausted

    pool = PagePool(n_pages=2, page_size=4)
    a, b = pool.alloc(), pool.alloc()
    assert pool.used() == 2 and pool.peak_used == 2
    pool.register(a, np.arange(8), 0, 4)
    pool.share(a)
    pool.release(a)
    assert pool.used() == 2                 # still mapped once
    pool.release(a)
    assert pool.used() == 2 and a in pool.cold   # registered -> cold, not free
    pool.release(b)
    assert pool.used() == 1                 # unregistered -> freed
    pool.alloc()                            # takes the free page...
    pool.alloc()                            # ...then reclaims cold a
    assert pool.n_cold_reclaims == 1
    assert not pool.full_map and not pool.meta   # a's registration dropped
    with pytest.raises(PoolExhausted):
        pool.alloc()


def test_page_pool_prefix_matching():
    pool = PagePool(n_pages=8, page_size=4)
    toks = np.arange(100, 110, dtype=np.int32)   # 10 tokens: 2 full + tail
    pids = [pool.alloc() for _ in range(3)]
    for j, pid in enumerate(pids):
        pool.register(pid, toks, j * 4, min(4, 10 - j * 4))
    # identical prompt, limit one short of the end: both full pages match,
    # then the tail page partially
    full, partial = pool.match_prefix(toks, limit=9)
    assert full == pids[:2]
    assert partial == (pids[2], 1)
    # divergence inside page 1: only page 0 matches fully, page 1 partially
    div = toks.copy()
    div[6] = 7
    full, partial = pool.match_prefix(div, limit=9)
    assert full == pids[:1]
    assert partial == (pids[1], 2)
    # nothing shared
    full, partial = pool.match_prefix(np.arange(5, dtype=np.int32), limit=4)
    assert full == [] and partial is None


# ---------------------------------------------------------------------------
# parity: paged mixed-length decode is bit-identical per request
# ---------------------------------------------------------------------------

def test_mixed_lengths_bit_identical_to_unbatched(model, engine):
    """4 mixed-length requests through 2 slots: chunked prefills, slot
    reuse, idle-row drop-writes — every stream must match unbatched greedy
    AND the per-slot ContinuousEngine on the same workload."""
    cfg, params = model
    rng = np.random.default_rng(0)
    lens = [5, 11, 17, 8]
    prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in lens]
    for i, pr in enumerate(prompts):
        engine.submit(Request(request_id=i, prompt=pr, max_new_tokens=6))
    done = engine.run()
    assert [r.request_id for r in done] == [0, 1, 2, 3]       # submit order
    for r in done:
        ref = _reference_decode(cfg, params, r.prompt, 6)
        assert r.output == ref, (r.request_id, r.output, ref)
        assert all(t < cfg.vocab_size for t in r.output)
    # chunked prefill really ran (17-token prompt needs 3 chunks of 8)
    assert engine.n_chunks > len(prompts)
    # per-slot engine parity on the identical workload
    with ContinuousEngine(cfg, params,
                          ServeConfig(max_batch=2, max_len=48)) as cont:
        for i, pr in enumerate(prompts):
            cont.submit(Request(request_id=i, prompt=pr, max_new_tokens=6))
        cont_done = cont.run()
    assert [r.output for r in done] == [r.output for r in cont_done]


def test_prefix_sharing_maps_pages_and_stays_exact(model, engine):
    """Two prompts sharing a 2-page prefix: the second maps the first's
    pages (no recompute) and still decodes bit-identically."""
    cfg, params = model
    rng = np.random.default_rng(1)
    base = rng.integers(1, cfg.vocab_size, size=16).astype(np.int32)
    tails = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
             for n in (4, 7)]
    pa, pb = (np.concatenate([base, t]) for t in tails)
    engine.submit(Request(request_id=10, prompt=pa, max_new_tokens=5))
    ra = engine.run()[0]
    shared0, chunks0 = engine.n_shared_pages, engine.n_chunks
    engine.submit(Request(request_id=11, prompt=pb, max_new_tokens=5))
    rb = engine.run()[0]
    assert engine.n_shared_pages - shared0 == 2      # both full base pages
    assert engine.n_chunks - chunks0 == 1            # only the tail prefilled
    assert ra.output == _reference_decode(cfg, params, pa, 5)
    assert rb.output == _reference_decode(cfg, params, pb, 5)


def test_cow_mid_page_divergence_no_corruption(model, engine):
    """A prompt diverging mid-page CoWs the partial match; the original
    prompt's stream must be unchanged afterwards (the shared page was
    copied, not mutated)."""
    cfg, params = model
    rng = np.random.default_rng(2)
    pa = rng.integers(1, cfg.vocab_size, size=20).astype(np.int32)
    engine.submit(Request(request_id=20, prompt=pa, max_new_tokens=5))
    ra = engine.run()[0]
    assert ra.output == _reference_decode(cfg, params, pa, 5)
    # diverge at position 19 — inside the third 8-token page
    pc = pa.copy()
    pc[19] = int(pa[19] % (cfg.vocab_size - 1)) + 1
    cow0 = engine.n_cow_copies
    engine.submit(Request(request_id=21, prompt=pc, max_new_tokens=5))
    rc = engine.run()[0]
    assert engine.n_cow_copies > cow0
    assert rc.output == _reference_decode(cfg, params, pc, 5)
    # the original prefix pages were not corrupted by the divergent request
    engine.submit(Request(request_id=22, prompt=pa, max_new_tokens=5))
    assert engine.run()[0].output == ra.output


def test_pool_exhaustion_evicts_and_recomputes_identically(model):
    """A pool too small for both requests: the younger is evicted mid-
    flight, requeued, and recomputed — both token streams stay exact."""
    cfg, params = model
    rng = np.random.default_rng(3)
    p1, p2 = (rng.integers(1, cfg.vocab_size, size=20).astype(np.int32)
              for _ in range(2))
    with PagedEngine(cfg, params, ServeConfig(max_batch=2, max_len=48),
                     paged=PagedConfig(page_size=8, prefill_chunk=8,
                                       n_pages=6, share_prefix=False)) as eng:
        eng.submit(Request(request_id=0, prompt=p1, max_new_tokens=8))
        eng.submit(Request(request_id=1, prompt=p2, max_new_tokens=8))
        done = eng.run()
        assert eng.n_evictions > 0
        assert eng.page_pool.peak_used <= 6
    assert [r.request_id for r in done] == [0, 1]
    assert done[0].output == _reference_decode(cfg, params, p1, 8)
    assert done[1].output == _reference_decode(cfg, params, p2, 8)


def test_chunked_prefill_keeps_decode_flowing(model, engine):
    """While a long prompt prefills chunk by chunk, the active request keeps
    emitting one token per step — decode latency is bounded by the chunk
    size, never by a stranger's prompt length."""
    cfg, params = model
    rng = np.random.default_rng(4)
    pa = rng.integers(1, cfg.vocab_size, size=5).astype(np.int32)
    a = Request(request_id=30, prompt=pa, max_new_tokens=12)
    engine.submit(a)
    while not a.output:
        engine.step()
    plong = rng.integers(1, cfg.vocab_size, size=33).astype(np.int32)
    lg = Request(request_id=31, prompt=plong, max_new_tokens=4)
    engine.submit(lg)
    stalls, prefill_steps = 0, 0
    while not lg.output and not a.done:
        before = len(a.output)
        engine.step()
        prefill_steps += 1
        stalls += (len(a.output) == before)
    assert prefill_steps >= 4          # 33 tokens / 8-token chunks
    assert stalls == 0                 # a emitted on every one of those steps
    done = engine.run()
    assert a.output == _reference_decode(cfg, params, pa, 12)
    assert lg.output == _reference_decode(cfg, params, plong, 4)
    assert {r.request_id for r in done} == {30, 31}


# ---------------------------------------------------------------------------
# admission / construction guards
# ---------------------------------------------------------------------------

def test_submit_validation(engine):
    with pytest.raises(ValueError, match="empty prompt"):
        engine.submit(Request(request_id=0, prompt=np.empty(0, np.int32)))
    with pytest.raises(ValueError, match="max_new_tokens"):
        engine.submit(Request(request_id=0, prompt=np.ones(4, np.int32),
                              max_new_tokens=0))
    with pytest.raises(ValueError, match="exceeds max_len"):
        engine.submit(Request(request_id=0, prompt=np.ones(40, np.int32),
                              max_new_tokens=40))


def test_rejects_unsupported_archs(model):
    cfg, params = model
    with pytest.raises(ValueError, match="attention-only"):
        PagedEngine(cfg.reduced(frontend="audio"), params,
                    ServeConfig(max_batch=2, max_len=16))
    with pytest.raises(ValueError, match="cannot hold"):
        PagedEngine(cfg, params, ServeConfig(max_batch=2, max_len=48),
                    paged=PagedConfig(page_size=8, n_pages=2))


def test_static_decode_plan_is_default(engine):
    assert engine.decode_host_mode == "static"
    assert engine.n_executors >= 1


@pytest.mark.stress
def test_repeated_eviction_under_sustained_pressure_stays_exact(model):
    """ISSUE 9 satellite: a page pool held at the edge of exhaustion across
    a stream of staggered requests forces eviction + requeue + chunked
    recompute over and over; every stream must stay bit-exact and the pool
    must never exceed its physical page budget."""
    cfg, params = model
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, cfg.vocab_size,
                            size=int(rng.integers(12, 24))).astype(np.int32)
               for _ in range(6)]
    with PagedEngine(cfg, params, ServeConfig(max_batch=2, max_len=48),
                     paged=PagedConfig(page_size=8, prefill_chunk=8,
                                       n_pages=6, share_prefix=False)) as eng:
        reqs = [Request(request_id=i, prompt=p, max_new_tokens=6)
                for i, p in enumerate(prompts)]
        # staggered submission keeps admission churning against eviction
        for i, r in enumerate(reqs):
            eng.submit(r)
            if i % 2 == 1:
                for _ in range(3):
                    if eng.has_work:
                        eng.step()
        done = eng.run()
        assert eng.n_evictions >= 2, "pressure never forced repeat evictions"
        assert eng.page_pool.peak_used <= 6
    assert sorted(r.request_id for r in done) == list(range(6))
    for r in sorted(done, key=lambda r: r.request_id):
        assert r.output == _reference_decode(cfg, params, prompts[r.request_id], 6), \
            f"request {r.request_id} diverged after eviction/recompute"


# ---------------------------------------------------------------------------
# the pool stays in place: every write donates it
# ---------------------------------------------------------------------------

def test_overlapped_chunks_and_cow_stay_exact(model):
    """Shared prefixes that diverge mid-page (copy-on-write) admitted while
    other requests decode (chunks overlapped with the decode step, both
    reading one pool): every stream matches unbatched greedy."""
    cfg, params = model
    rng = np.random.default_rng(5)
    base = rng.integers(1, cfg.vocab_size, size=20).astype(np.int32)
    prompts = [base]
    for cut in (19, 13):                       # diverge inside pages 2 and 1
        p = base.copy()
        p[cut] = int(base[cut] % (cfg.vocab_size - 1)) + 1
        prompts.append(np.concatenate(
            [p, rng.integers(1, cfg.vocab_size, size=9).astype(np.int32)]))
    with PagedEngine(cfg, params, ServeConfig(max_batch=3, max_len=48),
                     paged=PagedConfig(page_size=8, prefill_chunk=8)) as eng:
        first = Request(request_id=0, prompt=prompts[0], max_new_tokens=10)
        eng.submit(first)
        while not first.output:
            eng.step()
        for i, p in enumerate(prompts[1:], start=1):
            eng.submit(Request(request_id=i, prompt=p, max_new_tokens=6))
        done = eng.run()
        assert eng.n_cow_copies >= 2 and eng.n_overlapped_chunks > 0
    for r in done:
        assert r.output == _reference_decode(
            cfg, params, r.prompt, r.max_new_tokens), r.request_id


def test_pool_writes_donate_the_pool(model):
    """Each pool write — a chunk insert, the decode step's install, a
    copy-on-write — consumes the engine's pool: the array it held is
    deleted and the engine holds only the updated one."""
    cfg, params = model
    rng = np.random.default_rng(6)
    prompt = rng.integers(1, cfg.vocab_size, size=12).astype(np.int32)
    with PagedEngine(cfg, params, ServeConfig(max_batch=2, max_len=48),
                     paged=PagedConfig(page_size=8, prefill_chunk=8)) as eng:
        def step_replaces_pool() -> None:
            held = jax.tree.leaves(eng._pages)
            eng.step()
            assert all(a.is_deleted() for a in held)
            assert not any(a.is_deleted() for a in jax.tree.leaves(eng._pages))

        eng.submit(Request(request_id=0, prompt=prompt, max_new_tokens=4))
        step_replaces_pool()                   # chunk insert
        step_replaces_pool()                   # chunk insert, first token
        step_replaces_pool()                   # decode install
        assert eng.n_decode_steps == 1
        eng.run()
        cow = prompt.copy()
        cow[10] = cow[10] % (cfg.vocab_size - 1) + 1   # diverge in page 1
        eng.submit(Request(request_id=1, prompt=cow, max_new_tokens=2))
        step_replaces_pool()                   # copy-on-write, then insert
        assert eng.n_cow_copies == 1
        eng.run()
