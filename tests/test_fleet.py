"""Fleet tier: routing, supervision, failover, bit-exact replay.

The fast tests drive toy-engine fleets (worker processes spawn in ~100ms,
no jax in the children); the ``stress``-marked drills inject seeded faults
— SIGKILL mid-decode, a wedged serve loop, a live-but-muted replica — and
assert the tentpole contract: zero lost requests and bit-identical token
streams across failover.  CI runs the stress set in a dedicated job under
a hard wall-clock timeout.
"""
import time

import pytest

from repro.fleet import (Fleet, FleetConfig, FaultInjector, FaultSpec, Router,
                         WorkerStartupError, corrupt_lease_release)
from repro.fleet.worker import ToyEngine, toy_next_token

VOCAB = 101


def toy_cfg(n_workers, *, service=0.002, hb=0.05, inflight=3, **kw):
    return FleetConfig(
        n_workers=n_workers,
        engine={"kind": "toy", "vocab_size": VOCAB, "service_time_s": service},
        heartbeat_s=hb, max_inflight_per_worker=inflight, term_grace_s=0.3,
        **kw)


def reference(prompt, n):
    out = []
    for _ in range(n):
        out.append(toy_next_token(prompt, out, VOCAB, seed=0))
    return out


def assert_exact(done):
    for r in done:
        assert list(r.tokens) == reference(r.prompt, r.max_new), \
            f"request {r.rid} diverged after {r.n_requeues} requeue(s)"


# ---------------------------------------------------------------------------
# components
# ---------------------------------------------------------------------------

def test_toy_engine_resume_is_bit_exact():
    """Decoding 10 tokens straight == decoding 4, then resuming a fresh
    engine with those 4 as ``emitted`` — the replay contract in miniature."""
    e1 = ToyEngine(vocab_size=VOCAB)
    e1.submit(0, (3, 1, 4), 10)
    full = []
    while e1.has_work:
        full += [t for _, t, _, _ in e1.step()]

    e2 = ToyEngine(vocab_size=VOCAB)
    e2.submit(0, (3, 1, 4), 10, emitted=full[:4])
    resumed = list(full[:4])
    while e2.has_work:
        for _, tok, idx, _ in e2.step():
            assert idx == len(resumed)
            resumed.append(tok)
    assert resumed == full == reference((3, 1, 4), 10)


def test_router_prefers_affinity_then_load():
    r = Router(affinity_len=4, max_load_gap=2)
    for w in (0, 1, 2):
        r.add_worker(w)
    cap = {0: 4, 1: 4, 2: 4}
    prompt = (7, 7, 7, 7, 9)
    first = r.pick(prompt, capacity=cap)
    assert first == 0                      # all empty: lowest id wins
    # same prefix routes back to the same worker (affinity hit)
    assert r.pick((7, 7, 7, 7, 1), capacity=cap) == first
    assert r.n_affinity_hits == 1
    # a different prefix goes to the least-loaded worker, not worker 0
    assert r.pick((8, 8, 8, 8), capacity=cap) == 1
    # affinity yields once the load gap exceeds max_load_gap
    for _ in range(3):
        r.pick(prompt, capacity=cap)       # pile onto worker 0 (load 5)
    assert r.pick((7, 7, 7, 7, 2), capacity={0: 1, 1: 4, 2: 4}) != 0


def test_router_full_fleet_returns_none_and_forgets_dead_workers():
    r = Router()
    r.add_worker(0)
    assert r.pick((1, 2), capacity={0: 0}) is None
    assert r.pick((1, 2), capacity={0: 1}) == 0
    r.remove_worker(0)
    assert r.pick((1, 2), capacity={0: 3}) is None   # dead: not routable


# ---------------------------------------------------------------------------
# healthy-fleet behaviour
# ---------------------------------------------------------------------------

def test_fleet_drains_bit_exact_and_in_submit_order():
    reqs = [([i, i + 1], 8) for i in range(7)]
    with Fleet(toy_cfg(2)) as fleet:
        done = fleet.run(reqs, timeout_s=60)
        stats = fleet.stats()
    assert [r.rid for r in done] == sorted(r.rid for r in done)
    assert len(done) == 7
    assert_exact(done)
    assert stats["n_failovers"] == 0
    assert stats["router_routed"] == 7


def test_fleet_streams_tokens_in_order():
    seen: dict[int, list] = {}
    with Fleet(toy_cfg(2)) as fleet:
        fleet.on_token = lambda rid, tok, idx: seen.setdefault(rid, []).append(
            (idx, tok))
        done = fleet.run([([1, 2, 3], 6), ([4, 5], 6)], timeout_s=60)
    for r in done:
        assert [i for i, _ in seen[r.rid]] == list(range(r.max_new))
        assert [t for _, t in seen[r.rid]] == list(r.tokens)


def test_fleet_same_prompt_hits_same_replica():
    prompt = [9] * 20
    with Fleet(toy_cfg(2, inflight=8)) as fleet:
        fleet.run([(prompt, 4) for _ in range(6)], timeout_s=60)
        stats = fleet.stats()
    assert stats["router_affinity_hits"] >= 5


# ---------------------------------------------------------------------------
# fault drills (stress: dedicated CI job, hard timeout)
# ---------------------------------------------------------------------------

@pytest.mark.stress
@pytest.mark.parametrize("kind", ["kill", "die", "stall", "mute"])
def test_fleet_failover_zero_loss_bit_exact(kind):
    """The tentpole drill: kill/wedge/mute a replica mid-decode; every
    request still completes with a bit-identical stream."""
    reqs = [([i, i + 2], 16) for i in range(8)]
    with Fleet(toy_cfg(4, inflight=2)) as fleet:
        inj = FaultInjector(
            [FaultSpec(kind=kind, at_tokens=12, duration_s=5.0)], seed=3)
        done = fleet.run(reqs, injector=inj, timeout_s=120)
        stats = fleet.stats()
    assert len(done) == len(reqs), "lost requests across failover"
    assert_exact(done)
    assert inj.all_fired
    assert stats["n_failovers"] >= 1
    assert stats["n_requeued"] >= 1
    assert stats["n_restarts"] >= 1


@pytest.mark.stress
def test_fleet_survives_two_sequential_kills():
    reqs = [([i], 20) for i in range(8)]
    with Fleet(toy_cfg(3, inflight=3, max_restarts=4)) as fleet:
        inj = FaultInjector([FaultSpec(kind="kill", at_tokens=20),
                             FaultSpec(kind="kill", at_tokens=80)], seed=11)
        done = fleet.run(reqs, injector=inj, timeout_s=120)
        stats = fleet.stats()
    assert len(done) == len(reqs)
    assert_exact(done)
    assert stats["n_failovers"] == 2
    # the killed slots respawned with bumped generations
    assert sum(stats["generations"].values()) == 2


@pytest.mark.stress
def test_fleet_short_mute_flushes_buffered_stream():
    """A mute shorter than the liveness deadline must NOT fail the worker:
    the buffered tokens flush in order and indices stay contiguous."""
    cfg = toy_cfg(1, inflight=4, liveness_s=2.0)
    with Fleet(cfg) as fleet:
        inj = FaultInjector(
            [FaultSpec(kind="mute", at_tokens=4, duration_s=0.3)], seed=0)
        done = fleet.run([([1, 2], 24), ([3, 4], 24)], injector=inj,
                         timeout_s=60)
        stats = fleet.stats()
    assert stats["n_failovers"] == 0
    assert len(done) == 2
    assert_exact(done)


@pytest.mark.stress
def test_fleet_wedge_is_detected_by_silence():
    """A stalled serve loop sends no heartbeats; the liveness deadline —
    not a crash — must trigger the failover."""
    reqs = [([i, i], 16) for i in range(4)]
    with Fleet(toy_cfg(2, inflight=2)) as fleet:
        inj = FaultInjector(
            [FaultSpec(kind="stall", at_tokens=8, duration_s=10.0)], seed=5)
        t0 = time.monotonic()
        done = fleet.run(reqs, injector=inj, timeout_s=120)
        wall = time.monotonic() - t0
        events = list(fleet.events)
    assert len(done) == len(reqs)
    assert_exact(done)
    fails = [(t, why) for t, kind, _, why in events if kind == "fail"]
    assert fails and "silent" in fails[0][1]
    assert wall < 10.0, "drain waited for the stall instead of failing over"


def test_fleet_restart_budget_exhaustion_raises():
    with pytest.raises(RuntimeError, match="restart budget"):
        with Fleet(toy_cfg(1, max_restarts=0)) as fleet:
            fleet.submit([1, 2], 50)
            inj = FaultInjector([FaultSpec(kind="kill", at_tokens=2)], seed=0)
            fleet.run(timeout_s=60, injector=inj)


def test_fleet_stops_when_a_worker_cannot_start():
    """An engine that raises in its constructor is a start-up fault, not a
    crash to fail over: the fleet stops with the worker's exit code and
    error instead of burning the restart budget on respawns."""
    cfg = FleetConfig(n_workers=2, engine={"kind": "paged", "arch": "no-such-arch"},
                      heartbeat_s=0.05, term_grace_s=0.3)
    fleet = Fleet(cfg)
    with pytest.raises(WorkerStartupError) as err:
        fleet.wait_ready(timeout_s=120)
    assert err.value.exitcode == 1
    assert "no_such_arch" in err.value.error
    assert fleet.n_restarts == 0
    assert fleet.stats()["n_workers"] == 0          # every replica stopped


# ---------------------------------------------------------------------------
# runtime-level fault: corrupted lease release
# ---------------------------------------------------------------------------

def test_corrupt_lease_release_is_absorbed():
    import repro

    rt = repro.Runtime(3)
    try:
        health = corrupt_lease_release(rt, width=2)
        assert health["bad_releases"] >= 2        # double + stale release
        assert health["free"] == 3                # free list intact
        lease = rt.lease(3)                       # full width still grantable
        lease.release()
    finally:
        rt.close()
