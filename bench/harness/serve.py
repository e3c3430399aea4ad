"""Serving cells: a closed loop of clients driving ``PagedEngine.step()``.

The engine is built as ``repro.launch.serve`` builds it (``Runtime`` ->
``api.compile`` -> calibrated static decode plan).  Set-up makes the
weights, builds the engine, serves one request per shared prefix, and
admits every client's first request; the window starts once all of them
are decoding, so the mix of request ages is steady from its first step.
Nothing is submitted after the window closes; steps go on only until every
request submitted in the window has its first token.
"""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field

import numpy as np

from . import flops, trace
from .traffic import ClosedLoop, seed_words


@dataclass
class Step:
    t0: float
    t1: float
    chunks: int            # prefill chunks the step ran
    decoded: bool          # whether it ran a decode step
    ctx: list              # attended positions of each decoding row
    in_window: bool = False


@dataclass
class ServeRecord:
    kind: str = "serve"
    setup_s: float = 0.0
    t0: float = 0.0        # window start, host clock
    t1: float = 0.0        # window end: end of its last step
    steps: list = field(default_factory=list)
    token_times: dict = field(default_factory=dict)    # request -> [t]
    submit_time: dict = field(default_factory=dict)    # request -> t
    prompt_len: dict = field(default_factory=dict)
    window_requests: list = field(default_factory=list)
    admitted: list = field(default_factory=list)       # admitted in window
    capacity: int = 0
    stats0: dict = field(default_factory=dict)         # engine.stats() at t0
    stats1: dict = field(default_factory=dict)         # ... and at t1
    page_size: int = 16
    trace: object = None                               # trace.Summary
    compiles_in_window: int = 0
    late_s: list = field(default_factory=list)         # submission delays
    model: dict = field(default_factory=dict)
    events: object = None                              # trace.Events, traced runs


# keys of a configuration file that describe it and size nothing
DESCRIPTIVE = ("source", "reference", "reduced", "published", "assumed",
               "deployment")


def model_config(m: dict):
    """The program's ``ModelConfig`` for configuration file ``m``: every key
    of the file that is a ``ModelConfig`` field (``family`` "dense" unless
    the file says), past the descriptive keys and those the architecture
    module reads itself (its ``OWN_KEYS``).  Any other key raises, so that
    a misspelt size fails instead of being dropped."""
    import dataclasses

    import jax.numpy as jnp

    from repro.configs.base import ModelConfig

    from .spec import architecture

    fields = {f.name: f for f in dataclasses.fields(ModelConfig)}
    skip = set(DESCRIPTIVE) | set(getattr(architecture(m), "OWN_KEYS", ()))
    unknown = sorted(k for k in m if k not in fields and k not in skip)
    if unknown:
        raise KeyError(f"configuration {m.get('name')!r}: keys {unknown} are "
                       f"neither ModelConfig fields nor read by "
                       f"{m['reference']}")
    kw = {"family": "dense"}
    for k, v in m.items():
        if k in skip:
            continue
        if k == "dtype":
            v = getattr(jnp, v)
        elif isinstance(v, list) and str(fields[k].type).startswith("tuple"):
            v = tuple(v)
        kw[k] = v
    return ModelConfig(**kw)


class Loop:
    """The closed loop around one engine: submits, steps, stamps tokens."""

    def __init__(self, engine, gen: ClosedLoop, rec: ServeRecord):
        from repro.serve.engine import Request

        self.Request = Request
        self.engine, self.gen, self.rec = engine, gen, rec
        self.live: dict = {}          # request id -> (Request, tokens seen)
        self.done: list = []          # finished Requests, in finishing order
        self.next_k = 0
        self.submitting = True
        self.in_window = False
        self.unadmitted: list = []    # submitted, not yet stepped

    def submit(self, plan) -> None:
        prompt = self.gen.prompt(plan)
        req = self.Request(request_id=plan.index, prompt=prompt,
                           max_new_tokens=plan.output_len, eos_id=None)
        self.rec.prompt_len[plan.index] = len(prompt)
        self.rec.token_times[plan.index] = []
        self.engine.submit(req)
        self.rec.submit_time[plan.index] = time.perf_counter()
        if self.in_window:
            self.rec.window_requests.append(plan.index)
        self.live[plan.index] = (req, 0)
        self.unadmitted.append(plan.index)

    def submit_next(self) -> None:
        self.submit(self.gen.plan(self.next_k))
        self.next_k += 1

    def step(self) -> Step:
        import jax

        if self.in_window:
            self.rec.admitted.extend(self.unadmitted)
        self.unadmitted.clear()
        ctx = [self.rec.prompt_len[i] + seen for i, (_, seen)
               in self.live.items() if seen > 0]
        before = self.engine.stats()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            self.engine.step()
        t1 = time.perf_counter()
        after = self.engine.stats()
        st = Step(t0, t1, after["n_chunks"] - before["n_chunks"],
                  after["n_decode_steps"] > before["n_decode_steps"],
                  ctx if after["n_decode_steps"] > before["n_decode_steps"]
                  else [], self.in_window)
        self.rec.steps.append(st)
        finished = 0
        for i, (req, seen) in list(self.live.items()):
            n = len(req.output)
            if n > seen:
                self.rec.token_times[i].extend([t1] * (n - seen))
                self.live[i] = (req, n)
            if req.done:
                del self.live[i]
                self.done.append(req)
                finished += 1
        if self.submitting:
            with jax.profiler.TraceAnnotation("bench.submit"):
                for _ in range(finished):
                    self.submit_next()
                    self.rec.late_s.append(time.perf_counter() - t1)
        return st


class CompileCounter:
    """Counts compilations (and loads from the persistent cache) while on."""

    def __init__(self):
        import jax

        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, name, _secs, **_kw):
        if self.on and name == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _ev(self, name, **_kw):
        if self.on and name == "/jax/compilation_cache/cache_hits":
            self.n += 1


def run(cell, seed: int, seconds: float, traced: bool, devices,
        t_start: float, state_dir, log) -> tuple[ServeRecord, dict]:
    """One run of a serving cell.  Returns the record and what the check
    needs (weights, sampled requests); the engine is gone by then."""
    import jax

    import repro
    from repro.serve.engine import ServeConfig
    from repro.serve.paged import PagedConfig, PagedEngine

    from . import weights

    m, t = cell.config, cell.traffic
    e = t["engine"]
    rec = ServeRecord(page_size=int(e["page_size"]), model=m,
                      capacity=int(e["max_batch"]))
    w_weights, w_sample = seed_words(seed, 2)
    params = weights.make(m, jax.random.key(w_weights))
    jax.block_until_ready(params)
    cfg = model_config(m)
    gen = ClosedLoop(t, seed, m["vocab_size"])
    runtime = repro.Runtime(
        calibration_path=str(state_dir / "calibration.json"))
    repro.set_default_runtime(runtime)
    engine = PagedEngine(
        cfg, params, ServeConfig(max_batch=int(e["max_batch"]),
                                 max_len=int(e["max_len"])),
        paged=PagedConfig(page_size=int(e["page_size"]),
                          n_pages=int(e["n_pages"]),
                          prefill_chunk=int(e["prefill_chunk"])),
        runtime=runtime, decode_host_mode="static", schedule_search="auto")
    log(f"host plan: {engine.n_executors} executors leased of "
        f"{runtime.n_workers}, team size {engine.profile.best_team_size}, "
        f"decode {engine.decode_host_mode}; {engine.capacity} slots, "
        f"{engine.page_pool.n_pages} pages of {e['page_size']}, chunk "
        f"{engine.chunk}")
    loop = Loop(engine, gen, rec)

    # set-up the traffic needs: the prefix cache, then every client admitted
    loop.submitting = False
    for p in gen.warm_plans():
        loop.submit(p)
    while loop.live:
        loop.step()
    loop.submitting = True
    for _ in range(gen.clients):
        loop.submit_next()
    while any(not rec.token_times[k] for k in range(gen.clients)):
        loop.step()

    counter = CompileCounter()
    rec.stats0 = engine.stats()
    loop.in_window = True
    counter.on = True
    log_dir = str(state_dir / "trace")
    if traced:
        trace.start(log_dir)
    rec.t0 = time.perf_counter()
    rec.setup_s = rec.t0 - t_start
    rec.late_s.clear()
    with jax.profiler.TraceAnnotation("bench.window"):
        while time.perf_counter() - rec.t0 < seconds:
            rec.t1 = loop.step().t1
    if traced:
        ev = trace.stop_and_load(log_dir)
        (lo, dur), = [(t0, d) for n, t0, d, _ in ev.host
                      if n == "bench.window"]
        rec.trace = trace.reduce(ev, lo, lo + dur)
        rec.events = ev
    counter.on = False
    rec.compiles_in_window = counter.n
    rec.stats1 = engine.stats()
    loop.in_window = False
    loop.submitting = False
    # answers come late, not never: drain until every request submitted in
    # the window has its first token
    deadline = time.perf_counter() + 60.0
    while (any(not rec.token_times[i] for i in rec.window_requests)
           and time.perf_counter() < deadline):
        loop.step()
    log(f"window: {len(rec.steps)} steps in all, compilations in window "
        f"{rec.compiles_in_window}, submission delay max "
        f"{max(rec.late_s, default=0.0) * 1e3:.3f} ms mean "
        f"{np.mean(rec.late_s) * 1e3 if rec.late_s else 0.0:.3f} ms, "
        f"evictions {rec.stats1['n_evictions'] - rec.stats0['n_evictions']}, "
        f"engine stats {rec.stats1}")

    finished = [r for r in loop.done
                if r.request_id >= 0 and rec.token_times[r.request_id]
                and rec.t0 < rec.token_times[r.request_id][-1] <= rec.t1]
    sample = pick_sample(finished, cell.limits["sample_requests"], w_sample)
    device_state = {"params": params, "requests": sample}
    del engine, loop
    runtime.close()
    gc.collect()
    return rec, device_state


def pick_sample(finished, n: int, word: int) -> list:
    """The longest finished request, and ``n - 1`` others drawn from the
    seed; as (prompt, served tokens) pairs."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (len(r.prompt) + len(r.output),
                                           -r.request_id))
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng(word)
    pick = [longest] + [rest[i] for i in sorted(
        rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False))]
    return [(np.asarray(r.prompt, np.int32), np.asarray(r.output, np.int32))
            for r in pick]


# -- what the readers read ----------------------------------------------------
def window_tokens(rec: ServeRecord) -> int:
    return sum(1 for ts in rec.token_times.values() for x in ts
               if rec.t0 < x <= rec.t1)


def gaps(rec: ServeRecord) -> list[float]:
    """Every gap between consecutive tokens of one request that ends in the
    window, in seconds."""
    out = []
    for ts in rec.token_times.values():
        out.extend(b - a for a, b in zip(ts, ts[1:]) if rec.t0 < b <= rec.t1)
    return out


def ttfts(rec: ServeRecord) -> list[float]:
    return [rec.token_times[i][0] - rec.submit_time[i]
            for i in rec.window_requests if rec.token_times[i]]


def window_steps(rec: ServeRecord) -> list[Step]:
    return [s for s in rec.steps if s.in_window]


def decode_flops(rec: ServeRecord) -> float:
    """Model operations of every token emitted in the window."""
    m = rec.model
    total = 0.0
    for i, ts in rec.token_times.items():
        p = rec.prompt_len[i]
        for j, x in enumerate(ts):
            if rec.t0 < x <= rec.t1:
                total += flops.forward_token_flops(m, p + j)
    return total


def counts(rec: ServeRecord) -> tuple[int, int]:
    """Requests submitted in the window, and those that never answered."""
    return (len(rec.window_requests),
            sum(1 for i in rec.window_requests if not rec.token_times[i]))


def readings(cell, state: dict) -> dict:
    """The numbers ``correct`` compares: the widest logit gap of a served
    token under the reference, over the sample."""
    from . import check

    if not state["requests"]:
        return {"max_logit_gap": None}
    return {"max_logit_gap": check.widest_gap(
        cell.config, state["params"], state["requests"],
        cell.traffic["engine"]["max_len"])}
