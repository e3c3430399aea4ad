"""Serving engines: continuous batching on the graphi runtime + the wave batcher.

:class:`ContinuousEngine` — the latency-oriented engine (the regime
DESIGN.md §6 describes): a persistent decode loop over a fixed-capacity
per-slot KV cache (``transformer.init_cache(per_slot=True)``).  Each batch
row is a request *slot* at its own decode position; new requests' prefills
are admitted into free slots **between decode steps** — overlapped with the
in-flight decode on the same executors — and a finished request frees
its slot immediately on EOS/budget, so no request ever stalls on a
stranger's long prompt.  Prefill and decode are captured via
``repro.api.compile(backend="host")``; the profiler's configuration search
picks the executor count at engine construction.

The engine owns **no executor threads**: each :meth:`step` leases its
calibrated executor width from a :class:`~repro.runtime.Runtime` (the
process default unless one is passed) and runs decode + admission prefills
inside that lease, so a serve engine and a trainer — or two engines —
share one machine-sized pool with bounded interference.  An explicit
``pool=`` reproduces the old shared-pool wiring and bypasses admission.

:class:`ServeEngine` — the throughput-oriented wave batcher kept as the
baseline: requests are grouped into waves of equal prompt length, one
batched prefill, then batched decode until every member finishes.

Both engines sample over the pad-masked vocabulary
(:func:`repro.serve.step.sample_tokens`), so emitted ids are always
``< cfg.vocab_size`` even though the unembedding spans ``padded_vocab``.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.cost_model import KNL7250, HardwareModel
from repro.core.engine import ExecutorPool
from repro.models import transformer
from repro.runtime import Runtime, default_runtime
from repro.serve.step import make_decode_step, make_prefill_step, sample_tokens

__all__ = ["Request", "ServeConfig", "ServeEngine", "ContinuousEngine"]


def _validate_submit(req: "Request", scfg: "ServeConfig") -> None:
    """Shared submit-time validation (both engines, and the paged engine)."""
    if len(req.prompt) == 0:
        raise ValueError(f"request {req.request_id}: empty prompt")
    if req.max_new_tokens <= 0:
        raise ValueError(
            f"request {req.request_id}: max_new_tokens must be positive "
            f"(got {req.max_new_tokens})"
        )
    if len(req.prompt) + req.max_new_tokens > scfg.max_len:
        raise ValueError(
            f"request {req.request_id}: prompt ({len(req.prompt)}) + "
            f"max_new_tokens ({req.max_new_tokens}) exceeds max_len "
            f"({scfg.max_len})"
        )


@dataclass
class Request:
    request_id: int
    prompt: np.ndarray              # [S] int32
    max_new_tokens: int = 32
    eos_id: int | None = None
    # filled by the engine:
    output: list[int] = field(default_factory=list)
    done: bool = False
    _order: int = field(default=-1, repr=False, compare=False)


@dataclass(frozen=True)
class ServeConfig:
    max_batch: int = 8              # wave width / continuous slot capacity
    max_len: int = 512
    temperature: float = 0.0        # 0 => greedy
    pad_id: int = 0


class _SamplerMixin:
    """Shared pad-masked sampling (greedy or temperature) with key threading."""

    cfg: ModelConfig
    scfg: ServeConfig
    _key: jax.Array

    def _sample_on_device(self, logits) -> jax.Array:
        """Dispatch sampling; the tokens stay on the device."""
        key = None
        if self.scfg.temperature > 0:
            self._key, key = jax.random.split(self._key)
        return sample_tokens(logits, self.cfg.vocab_size, self.scfg.temperature, key)

    def _sample(self, logits) -> np.ndarray:
        return np.asarray(self._sample_on_device(logits), np.int32)


class ServeEngine(_SamplerMixin):
    """Length-bucketed wave batcher (the throughput baseline).

    The KV cache's slot-position table is shared across a wave, so waves are
    bucketed to *equal prompt length* — batched decode stays bit-identical
    to unbatched (tests/test_serve_engine.py).  A wave stalls on its slowest
    member; for latency under staggered arrivals use
    :class:`ContinuousEngine`.
    """

    def __init__(self, cfg: ModelConfig, params, scfg: ServeConfig, *, rng_seed: int = 0):
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        self.queue: list[Request] = []
        self._n_submitted = 0
        self._key = jax.random.key(rng_seed)
        self._prefill = jax.jit(lambda p, c, b: transformer.prefill(cfg, p, b, c))
        self._decode = jax.jit(lambda p, c, t: transformer.decode_step(cfg, p, t, c))

    def submit(self, req: Request) -> None:
        _validate_submit(req, self.scfg)
        req._order = self._n_submitted
        self._n_submitted += 1
        self.queue.append(req)

    # -- one wave -------------------------------------------------------------
    def _run_wave(self, wave: Sequence[Request]) -> None:
        cfg, scfg = self.cfg, self.scfg
        B = len(wave)
        Ls = {len(r.prompt) for r in wave}
        if len(Ls) != 1:
            raise RuntimeError(
                f"wave mixes prompt lengths {sorted(Ls)} — waves are "
                "length-bucketed")
        toks = np.stack([r.prompt for r in wave]).astype(np.int32)
        cache = transformer.init_cache(cfg, B, scfg.max_len)
        logits, cache = self._prefill(self.params, cache, {"tokens": jnp.asarray(toks)})

        active = np.ones(B, bool)
        budget = np.array([r.max_new_tokens for r in wave])
        n_emitted = np.zeros(B, int)
        while active.any():
            nxt_np = self._sample(logits)
            for i, r in enumerate(wave):
                if not active[i]:
                    continue
                t = int(nxt_np[i])
                r.output.append(t)
                n_emitted[i] += 1
                if (r.eos_id is not None and t == r.eos_id) or n_emitted[i] >= budget[i]:
                    active[i] = False
                    r.done = True
            if not active.any():
                break
            logits, cache = self._decode(self.params, cache, nxt_np[:, None])

    # -- public ----------------------------------------------------------------
    def run(self) -> list[Request]:
        """Drain the queue; returns completed requests in submit order."""
        buckets: dict[int, list[Request]] = {}
        for r in self.queue:
            buckets.setdefault(len(r.prompt), []).append(r)
        self.queue = []
        done: list[Request] = []
        for _, reqs in sorted(buckets.items()):
            for lo in range(0, len(reqs), self.scfg.max_batch):
                wave = reqs[lo : lo + self.scfg.max_batch]
                self._run_wave(wave)
                done.extend(wave)
        done.sort(key=lambda r: r._order)
        return done


class ContinuousEngine(_SamplerMixin):
    """Continuous-batching engine driven by graphi Executables.

    Construction captures the batched decode step and *calibrates* it:
    ``Executable.calibrate`` times every node fn on the decode shapes (the
    paper's first-iterations profiling) and the §4.2 configuration search
    picks ``n_executors × team_size`` from those measured costs, optionally
    bounded by ``max_executors``.  Prefill graphs are compiled per prompt
    length on demand, pinned to the same config, and share the decode
    graph's persistent executor pool — so an admission prefill runs
    *concurrently* with the in-flight decode step.

    The decode graph is fixed — one batch shape, replayed once per token —
    so steady-state steps execute it through a compiled
    :class:`~repro.core.static_host.StaticHostPlan`
    (``decode_host_mode="static"``): frozen CPF placements, lock-free
    dependency counters, no per-op scheduler round-trip.  Everything that
    coexists with admissions stays dynamic: prefill graphs (shapes vary
    per prompt length), and the decode step itself on the steps where
    prefills are in flight — a plan's segments would hold every executor
    for the whole step, while the dynamic scheduler interleaves per-op
    with the concurrent prefills.  ``decode_host_mode="dynamic"`` restores
    the paper-faithful per-op scheduler everywhere for A/B measurement.

    Protocol per :meth:`step`:

    1. **admit** — pending requests claim free slots; their prefills run on
       the pool while the decode step for currently-active slots executes;
    2. **install** — each prefilled request's K/V lands in its slot
       (:func:`transformer.cache_insert_slot`), its first token is sampled
       from the prefill logits;
    3. **retire** — EOS/budget frees the slot immediately
       (:func:`transformer.cache_evict_slot`); the next step's admission
       fills it.

    Idle slots decode a pad token against an all-masked position table;
    their output is discarded and their cache rows are overwritten wholesale
    at the next insert, so active rows stay bit-identical to unbatched
    greedy decode (dense archs; MoE capacity routing couples batch rows and
    is only *approximately* parity-preserving, exactly as in wave batching).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        scfg: ServeConfig,
        *,
        rng_seed: int = 0,
        hw: HardwareModel = KNL7250,
        max_executors: int | None = None,
        pool: ExecutorPool | None = None,
        runtime: Runtime | None = None,
        decode_host_mode: str = "static",
        schedule_search: str = "auto",
        step_deadline_s: float | None = None,
    ):
        if cfg.frontend:
            raise ValueError("continuous batching supports decoder-only archs "
                             f"(got frontend={cfg.frontend!r})")
        from repro import api

        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        self.hw = hw
        # per-step deadline: every graph run inside one step() carries
        # deadline = step start + step_deadline_s, so a hung op raises
        # DeadlineExceeded (quarantining its executor) instead of wedging
        # the engine loop — the in-process analogue of the fleet's
        # SIGKILL-after-silence.  None = wait forever (the default).
        self.step_deadline_s = step_deadline_s
        self._step_deadline: float | None = None
        self._key = jax.random.key(rng_seed)
        self.capacity = scfg.max_batch
        self.cache = transformer.init_cache(cfg, self.capacity, scfg.max_len, per_slot=True)
        self._zero_sub_cache = transformer.init_cache(cfg, 1, scfg.max_len, per_slot=True)

        # executors come from the process Runtime (leased per step) unless
        # the caller hands an explicit shared pool, which bypasses admission
        self.pool = pool
        self.runtime = runtime if runtime is not None else (
            None if pool is not None else default_runtime())

        # the decode graph is *fixed* (one shape, replayed once per token):
        # the compiled static host plan takes the scheduler off its hot path
        # entirely.  Prefill graphs stay dynamic — their shapes vary per
        # prompt length and they share the step's executors with the
        # in-flight decode.
        tok_spec = jax.ShapeDtypeStruct((self.capacity, 1), jnp.int32)
        # schedule_search="auto" (default): once the decode graph is
        # calibrated below, the frozen decode plan is the simulator-searched
        # min-makespan winner (persisted per graph signature), not bare CPF
        self._decode_exe = api.compile(
            make_decode_step(cfg), params, self.cache, tok_spec,
            hw=hw, backend="host", jit_nodes=True, host_mode=decode_host_mode,
            pool=pool, runtime=self.runtime, schedule_search=schedule_search,
            name=f"serve_decode[{cfg.name}]",
        )
        self.schedule_search = schedule_search
        self.decode_host_mode = self._decode_exe.host_mode
        # profile-guided executor config for the serving graph: the §4.2
        # search over *measured* per-op costs (Executable.calibrate runs the
        # paper's first-iterations profiling, jit-compiling every node fn as
        # a side effect).  Analytic flops misrank tiny jitted decode ops —
        # their cost is dispatch, not arithmetic — and the static plan
        # freezes the resulting placement, so it must come from real
        # timings.  A runtime calibration-store hit (same decode graph, a
        # prior engine or process) skips the measurement entirely.
        # Optionally bounded: serving should not claim the whole machine.
        if self._decode_exe.calibrated:
            kw = ({"max_executors": max_executors}
                  if max_executors is not None else {})
            self.profile = self._decode_exe.profile_with(**kw)
        else:
            self.profile = self._decode_exe.calibrate(
                params, jax.tree.map(jnp.zeros_like, self.cache),
                jnp.full((self.capacity, 1), scfg.pad_id, jnp.int32),
                max_executors=max_executors)
        n_exec = self._decode_exe.planned_executors
        if max_executors is not None:
            n_exec = max(1, min(n_exec, max_executors))
        if pool is not None:
            n_exec = min(n_exec, pool.n_executors)
        elif self.runtime is not None:
            n_exec = min(n_exec, self.runtime.n_workers)
        self.n_executors = n_exec
        self._step_lease_ids: tuple[int, ...] = ()
        if self._decode_exe.host_mode == "static":
            # freeze the plan now (not on the first request) at the planned
            # width — a pool or runtime wider than the calibrated config
            # must not widen the placement
            self._decode_exe.host_plan(n_exec)
        self._team_size = self.profile.best_team_size
        # prefill graphs are keyed by *bucket*, not exact prompt length:
        # prompts are right-padded to the next power of two and masked with a
        # valid-length (transformer.prefill's valid_len path), so N distinct
        # lengths compile O(log N) executables instead of N.  Bit-exactness
        # holds for dense attention-only archs — padded tokens never enter a
        # real token's causal window and their cache entries are pos-masked —
        # but MoE capacity routing couples positions, and SSM/RG-LRU carry
        # state through padding, so those archs keep exact-length graphs.
        self._bucket_prefill = (
            not cfg.n_experts and all(k == "attn" for k in cfg.layer_kinds()))
        self._prefill_cap = transformer._attn_cache_len(cfg, scfg.max_len)
        self._prefill_exes: dict[int, api.Executable] = {}

        # slot insert/evict are jitted with a *traced* slot index: one
        # compile covers every slot (XLA scatter compiles are slow, and the
        # admission path runs per request)
        self._insert = jax.jit(
            lambda cache, sub, slot: transformer.cache_insert_slot(cfg, cache, sub, slot))
        self._evict = jax.jit(
            lambda cache, slot: transformer.cache_evict_slot(cfg, cache, slot))

        self.slots: list[Request | None] = [None] * self.capacity
        self.pending: deque[Request] = deque()
        self.completed: list[Request] = []
        self._tokens = np.full((self.capacity, 1), scfg.pad_id, np.int32)
        self._n_submitted = 0
        # loop counters (benchmarks read these)
        self.n_steps = 0
        self.n_decode_steps = 0
        self.n_overlapped_prefills = 0
        # warm every per-step code path against throwaway state (first
        # executions compile per-shape kernels), so the serving loop runs at
        # steady-state cost from the first request on
        warm = jax.tree.map(jnp.zeros_like, self.cache)
        with self._step_pool() as wpool:
            logits, _ = self._run_exe(
                self._decode_exe, (params, warm, jnp.asarray(self._tokens)),
                pool=wpool)
            if self._decode_exe.host_mode == "static":
                # steps with admissions in flight fall back to the dynamic
                # scheduler (_decode_once) — warm that path's state too
                self._run_exe(
                    self._decode_exe, (params, warm, jnp.asarray(self._tokens)),
                    pool=wpool, host_mode="dynamic")
        sample_tokens(logits, cfg.vocab_size, scfg.temperature,
                      jax.random.key(0) if scfg.temperature > 0 else None)
        warm = self._insert(warm, self._zero_sub_cache, jnp.int32(0))
        warm = self._evict(warm, jnp.int32(0))
        jax.block_until_ready(warm["len"])

    # -- lifecycle -------------------------------------------------------------
    def close(self) -> None:
        """Nothing to release: the engine leases executors per step from the
        runtime (an explicit ``pool`` is the caller's to close).  Kept so
        engine call sites stay context-manager shaped."""

    def __enter__(self) -> "ContinuousEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- submission ------------------------------------------------------------
    def submit(self, req: Request) -> None:
        _validate_submit(req, self.scfg)
        req._order = self._n_submitted
        self._n_submitted += 1
        self.pending.append(req)

    @property
    def has_work(self) -> bool:
        return bool(self.pending) or any(s is not None for s in self.slots)

    def warmup(self, prompt_lens) -> None:
        """Pre-build + warm the prefill graphs for the given prompt lengths
        (deploy-time shape warming; admission then runs at steady-state)."""
        for s in sorted(set(int(x) for x in prompt_lens)):
            self._prefill_exe(s)

    # -- internals -------------------------------------------------------------
    def _step_pool(self):
        """The executors one engine iteration runs on: the explicit shared
        pool, or a fresh :class:`~repro.runtime.ExecutorLease` of the
        engine's calibrated width — acquired at step start, released at
        step end, so concurrent engines/trainers queue instead of
        oversubscribing.  The previous step's executor ids are passed as
        the affinity hint: the steady-state decode loop keeps its warm
        executor threads."""
        if self.pool is not None:
            return nullcontext(self.pool)
        lease = self.runtime.lease(self.n_executors,
                                   prefer=self._step_lease_ids)
        self._step_lease_ids = lease.executor_ids
        return lease

    def _run_exe(self, exe, args: tuple, *, pool, host_mode: str | None = None):
        """Execute a captured engine graph on the step's executors and
        unflatten to the fn's output pytree."""
        res = exe.execute_host(
            exe.captured.bind(args), n_executors=self.n_executors,
            pool=pool, host_mode=host_mode, deadline=self._step_deadline,
        )
        return exe.captured.unflatten(res.outputs)

    def _prefill_bucket(self, prompt_len: int) -> int:
        """Power-of-two length bucket (capped at the cache length); exact
        length for archs where padding would not be bit-exact, or when the
        cap falls below the prompt (SWA ring: no room to pad)."""
        if not self._bucket_prefill:
            return prompt_len
        b = 1 << max(0, prompt_len - 1).bit_length()
        b = min(b, self._prefill_cap)
        return b if b >= prompt_len else prompt_len

    def _prefill_batch(self, prompt) -> dict:
        S = len(prompt)
        bucket = self._prefill_bucket(S)
        if not self._bucket_prefill:
            return {"tokens": jnp.asarray(prompt, jnp.int32)[None]}
        toks = np.full((1, bucket), self.scfg.pad_id, np.int32)
        toks[0, :S] = prompt
        return {"tokens": jnp.asarray(toks), "valid_len": jnp.int32(S)}

    def _prefill_exe(self, prompt_len: int, pool=None):
        bucket = self._prefill_bucket(prompt_len)
        exe = self._prefill_exes.get(bucket)
        if exe is None:
            from repro import api

            tok_spec = {"tokens": jax.ShapeDtypeStruct((1, bucket), jnp.int32)}
            if self._bucket_prefill:
                tok_spec["valid_len"] = jax.ShapeDtypeStruct((), jnp.int32)
            exe = api.compile(
                make_prefill_step(self.cfg), self.params, self._zero_sub_cache, tok_spec,
                hw=self.hw, backend="host", pool=self.pool, runtime=self.runtime,
                jit_nodes=True, schedule_search=self.schedule_search,
                n_executors=self.n_executors, team_size=self._team_size,
                name=f"serve_prefill[{self.cfg.name},S={bucket}]",
            )
            # first-call warmup, same reasoning as the decode graph
            warm_batch = {"tokens": jnp.zeros((1, bucket), jnp.int32)}
            if self._bucket_prefill:
                warm_batch["valid_len"] = jnp.int32(bucket)
            out = self._run_exe(
                exe, (self.params, self._zero_sub_cache, warm_batch),
                pool=pool)
            sample_tokens(out[0], self.cfg.vocab_size, self.scfg.temperature,
                          jax.random.key(0) if self.scfg.temperature > 0 else None)
            jax.block_until_ready(out[0])
            self._prefill_exes[bucket] = exe
        return exe

    def _admit(self, req: Request, slot: int, pool=None):
        """Run the request's prefill graph on the step's executors."""
        exe = self._prefill_exe(len(req.prompt), pool=pool)
        logits, filled = self._run_exe(
            exe, (self.params, self._zero_sub_cache,
                  self._prefill_batch(req.prompt)),
            pool=pool)
        return req, slot, logits, filled

    def _install(self, req: Request, slot: int, logits, filled) -> None:
        """Land a prefilled request in its slot and sample its first token."""
        self.cache = self._insert(self.cache, filled, jnp.int32(slot))
        self.slots[slot] = req
        self._emit(slot, int(self._sample(logits)[0]))

    def _emit(self, slot: int, token: int) -> None:
        req = self.slots[slot]
        req.output.append(token)
        hit_eos = req.eos_id is not None and token == req.eos_id
        if hit_eos or len(req.output) >= req.max_new_tokens:
            req.done = True
            self.completed.append(req)
            self.slots[slot] = None
            self.cache = self._evict(self.cache, jnp.int32(slot))
            self._tokens[slot, 0] = self.scfg.pad_id
        else:
            self._tokens[slot, 0] = token

    def _decode_once(self, pool, *, overlapping_prefills: bool = False) -> None:
        exe = self._decode_exe
        host_mode = None
        if overlapping_prefills and exe.host_mode == "static":
            # a static plan's segments hold every one of the step's
            # executors for the whole decode, which would serialize the
            # concurrent admission prefills behind it; the dynamic scheduler
            # interleaves per-op, so steps with prefills in flight fall back
            # to it.  Steady-state steps (the vast majority) replay the
            # compiled plan.
            host_mode = "dynamic"
        logits, self.cache = self._run_exe(
            exe, (self.params, self.cache, jnp.asarray(self._tokens)),
            pool=pool, host_mode=host_mode)
        self.n_decode_steps += 1
        nxt = self._sample(logits)
        for i in range(self.capacity):
            if self.slots[i] is not None:
                self._emit(i, int(nxt[i]))

    # -- the loop --------------------------------------------------------------
    def step(self) -> bool:
        """One engine iteration: admit into free slots, one decode step.

        The step leases the engine's executors once (:meth:`_step_pool`);
        admission prefills execute concurrently with the decode step on
        those executors and their slots join the batch from the *next*
        step.  Returns whether work remains.
        """
        self.n_steps += 1
        if self.step_deadline_s is not None:
            self._step_deadline = time.monotonic() + self.step_deadline_s
        free = [i for i, s in enumerate(self.slots) if s is None]
        admits: list[tuple[Request, int]] = []
        while self.pending and free:
            admits.append((self.pending.popleft(), free.pop(0)))
        decoding = any(s is not None for s in self.slots)

        with self._step_pool() as pool:
            if admits and decoding:
                box: dict = {}

                def prefill_worker() -> None:
                    try:
                        box["res"] = [self._admit(r, s, pool=pool)
                                      for r, s in admits]
                    except BaseException as e:  # noqa: BLE001 — re-raised below
                        box["err"] = e

                th = threading.Thread(target=prefill_worker, name="serve-prefill")
                th.start()
                self._decode_once(pool, overlapping_prefills=True)
                th.join()
                if "err" in box:
                    raise box["err"]
                self.n_overlapped_prefills += len(admits)
                for item in box["res"]:
                    self._install(*item)
            elif admits:
                for r, s in admits:
                    self._install(*self._admit(r, s, pool=pool))
            elif decoding:
                self._decode_once(pool)
        self._step_deadline = None
        return self.has_work

    def run(self) -> list[Request]:
        """Drain pending + active requests; returns them in submit order."""
        while self.has_work:
            self.step()
        done = sorted(self.completed, key=lambda r: r._order)
        self.completed = []
        return done
