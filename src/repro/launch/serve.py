"""Serving CLI: ``python -m repro.launch.serve --arch gemma-2b --smoke``.

Builds a (randomly initialized) model, submits synthetic requests, and
reports decode throughput + per-request latency.  ``--continuous`` routes
through the graphi-scheduled :class:`ContinuousEngine` (prefill/decode
captured via ``repro.compile``, profiler-chosen executor config, slot
admission between decode steps, decode replayed through a compiled static
host plan unless ``--decode-host-mode dynamic``); the default is the wave
batcher.
``--arrival-rate`` staggers request arrivals (Poisson, requests/second)
instead of submitting everything up front.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs.base import get_config
from repro.launch.cache import enable_compile_cache
from repro.models import transformer
from repro.serve.engine import ContinuousEngine, Request, ServeConfig, ServeEngine
from repro.serve.paged import PagedConfig, PagedEngine


def build_requests(cfg, *, n_requests, prompt_lens, max_new,
                   arrival_rate=0.0, seed=0) -> list[tuple[float, Request]]:
    """(arrival_time, request) pairs: Poisson arrivals (all at t=0 when
    ``arrival_rate`` is 0), prompt lengths cycled from ``prompt_lens``.
    Shared by the CLI and ``scripts/bench_serve.py``."""
    rng = np.random.default_rng(seed)
    t = 0.0
    out = []
    for i in range(n_requests):
        if arrival_rate > 0:
            t += float(rng.exponential(1.0 / arrival_rate))
        prompt = rng.integers(
            1, cfg.vocab_size, size=prompt_lens[i % len(prompt_lens)]
        ).astype(np.int32)
        out.append((t, Request(request_id=i, prompt=prompt, max_new_tokens=max_new)))
    return out


def percentile(xs, q: float) -> float:
    """Index-based percentile of a sequence (0.0 when empty)."""
    xs = sorted(xs)
    if not xs:
        return 0.0
    return xs[min(len(xs) - 1, int(len(xs) * q))]


def drive(engine, arrivals: list[tuple[float, Request]], *, continuous: bool):
    """Feed requests at their arrival times; returns (done, latency, wall).

    The wave engine drains its queue whenever it is idle and work has
    arrived (its own granularity — one ``run()`` per busy period); the
    continuous engine steps, admitting arrivals between decode steps.
    """
    t0 = time.perf_counter()
    todo = list(arrivals)
    done: list[Request] = []
    finish: dict[int, float] = {}
    while True:
        now = time.perf_counter() - t0
        while todo and todo[0][0] <= now:
            engine.submit(todo.pop(0)[1])
        busy = engine.has_work if continuous else bool(engine.queue)
        if busy:
            if continuous:
                engine.step()
                for r in engine.completed:
                    if r.request_id not in finish:
                        finish[r.request_id] = time.perf_counter() - t0
            else:
                batch = engine.run()
                stamp = time.perf_counter() - t0
                for r in batch:
                    finish[r.request_id] = stamp
                    done.append(r)
        elif todo:
            time.sleep(max(0.0, todo[0][0] - (time.perf_counter() - t0)))
        else:
            break
    if continuous:
        done = engine.run()
    arrive = {r.request_id: t for t, r in arrivals}
    lat = {r.request_id: finish[r.request_id] - arrive[r.request_id] for r in done}
    return done, lat, time.perf_counter() - t0


def serve_fleet(args) -> int:
    """``--replicas N``: the supervised multi-replica tier.

    Spawns N worker processes under a :class:`repro.fleet.Fleet` —
    heartbeat liveness, crash/wedge failover with bit-exact replay, prefix-
    affinity routing — and drives the same Poisson workload through it.
    Workers default to real engines of the requested kind (sharing one JSON
    calibration store so replica 2..N skip the schedule search);
    ``--replica-engine toy`` swaps in the deterministic service-time worker
    the fleet tests/bench use.
    """
    import numpy as np

    from repro.fleet import Fleet, FleetConfig

    kind = args.replica_engine
    if kind == "auto":
        kind = "paged" if args.paged else "continuous"
    if kind == "toy":
        vocab = 256
        engine = {"kind": "toy", "vocab_size": vocab, "service_time_s": 0.004}
    else:
        cfg = get_config(args.arch, smoke=args.smoke)
        vocab = cfg.vocab_size
        engine = {"kind": kind, "arch": args.arch, "smoke": args.smoke,
                  "max_batch": args.max_batch,
                  "max_len": max(int(x) for x in
                                 str(args.prompt_len).split(",")) + args.max_new + 1,
                  "calibration_store": args.calibration_store}
    prompt_lens = [int(x) for x in str(args.prompt_len).split(",")]
    rng = np.random.default_rng(0)
    t, work = 0.0, []
    for i in range(args.requests):
        if args.arrival_rate > 0:
            t += float(rng.exponential(1.0 / args.arrival_rate))
        prompt = [int(x) for x in rng.integers(
            1, vocab, size=prompt_lens[i % len(prompt_lens)])]
        work.append((t, prompt, args.max_new))

    # real engines jit-compile their prefill/decode graphs on the *first*
    # steps after ready, and heartbeats ride the serve loop — the liveness
    # window must cover a compile-length step or the supervisor declares
    # every healthy replica wedged and burns the restart budget
    if kind == "toy":
        fcfg = FleetConfig(n_workers=args.replicas, engine=engine,
                           max_inflight_per_worker=args.max_batch)
    else:
        fcfg = FleetConfig(n_workers=args.replicas, engine=engine,
                           max_inflight_per_worker=args.max_batch,
                           heartbeat_s=0.5, liveness_s=120.0,
                           startup_grace_s=600.0)
    with Fleet(fcfg) as fleet:
        fleet.wait_ready()
        t0 = time.perf_counter()
        todo, arrive, finish = list(work), {}, {}
        while todo or fleet.has_work:
            now = time.perf_counter() - t0
            while todo and todo[0][0] <= now:
                at, prompt, max_new = todo.pop(0)
                arrive[fleet.submit(prompt, max_new)] = at
            fleet.pump()
            for req in fleet.completed:
                finish.setdefault(req.rid, time.perf_counter() - t0)
        done = sorted(fleet.completed, key=lambda r: r._order)
        wall = time.perf_counter() - t0
        stats = fleet.stats()
    n_tokens = sum(len(r.tokens) for r in done)
    lat = [finish[r.rid] - arrive[r.rid] for r in done]
    print(f"[fleet:{kind} x{args.replicas}] served {len(done)} requests, "
          f"{n_tokens} tokens in {wall:.2f}s ({n_tokens / wall:.1f} tok/s); "
          f"latency p50={percentile(lat, 0.5) * 1e3:.0f}ms "
          f"p95={percentile(lat, 0.95) * 1e3:.0f}ms")
    print(f"  failovers={stats['n_failovers']} requeued={stats['n_requeued']} "
          f"affinity_hits={stats['router_affinity_hits']}/"
          f"{stats['router_routed']}")
    bad = [t for r in done for t in r.tokens if t >= vocab]
    if bad:
        raise SystemExit(f"emitted out-of-vocab ids: {bad[:5]}")
    return 0


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--continuous", action="store_true",
                   help="continuous batching on the graphi runtime")
    p.add_argument("--paged", action="store_true",
                   help="block-paged KV cache with prefix sharing and "
                        "chunked prefill (implies continuous batching)")
    p.add_argument("--page-size", type=int, default=16,
                   help="tokens per physical KV page (--paged)")
    p.add_argument("--n-pages", type=int, default=None,
                   help="physical pages in the pool (--paged; default "
                        "max_batch * ceil(max_len/page_size))")
    p.add_argument("--prefill-chunk", type=int, default=64,
                   help="tokens prefilled per engine step per prompt "
                        "(--paged; rounded up to a page multiple)")
    p.add_argument("--arrival-rate", type=float, default=0.0,
                   help="Poisson arrival rate (req/s); 0 = all at once")
    def _positive(v):
        n = int(v)
        if n < 1:
            raise argparse.ArgumentTypeError("need at least 1 request")
        return n

    p.add_argument("--requests", type=_positive, default=8)
    p.add_argument("--prompt-len", default="32",
                   help="prompt length, or comma list for mixed lengths")
    p.add_argument("--max-new", type=int, default=32)
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--max-executors", type=int, default=None,
                   help="bound the profiler's executor-config search")
    p.add_argument("--decode-host-mode", choices=("static", "dynamic"),
                   default="static",
                   help="decode-graph runtime: compiled static host plan "
                        "(default) or the per-op dynamic scheduler")
    p.add_argument("--runtime-workers", type=int, default=None,
                   help="executor-thread count of the process Runtime "
                        "(default: machine core count)")
    p.add_argument("--calibration-store", default=None,
                   help="JSON path backing the Runtime's calibration store "
                        "(measured op costs survive restarts)")
    p.add_argument("--pinning", choices=("off", "auto", "on"), default="off",
                   help="pin executor threads to disjoint core sets "
                        "(repro.hwperf): 'auto' pins where the platform "
                        "supports affinity, 'on' warns once where it "
                        "doesn't (continuous/paged only)")
    p.add_argument("--dump-trace", choices=("ascii", "csv"), default=None,
                   help="print the decode executable's last execution "
                        "timeline (measured if available, else simulated) "
                        "after serving (continuous/paged only)")
    p.add_argument("--schedule-search", choices=("off", "auto", "force"),
                   default="auto",
                   help="simulator-guided schedule search over registered "
                        "policies: 'auto' (default) searches once the decode "
                        "graph is calibrated, 'force' always, 'off' plain "
                        "CPF; winners persist in the calibration store "
                        "(continuous/paged only)")
    p.add_argument("--check", choices=("off", "basic", "strict"),
                   default="off",
                   help="static verification (repro.checks) of the engine's "
                        "captured graphs/schedules/plans after build: "
                        "'basic' reports, 'strict' additionally refuses to "
                        "serve on error findings (continuous/paged only)")
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--replicas", type=int, default=1,
                   help="serve through a supervised multi-replica fleet "
                        "(worker processes, heartbeat failover, bit-exact "
                        "requeue) instead of one in-process engine")
    p.add_argument("--replica-engine", choices=("auto", "toy", "continuous",
                                                "paged"), default="auto",
                   help="fleet worker engine (--replicas > 1): 'auto' "
                        "follows --paged/--continuous, 'toy' is the "
                        "deterministic service-time worker")
    args = p.parse_args()

    if args.replicas > 1:
        return serve_fleet(args)

    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    # jitted: eager init holds float32 temporaries of the largest weights
    # beside the bf16 ones, and a 4 B-param model then overflows a 16 GB chip
    params = jax.jit(transformer.init_params, static_argnums=0)(
        cfg, jax.random.key(0))
    prompt_lens = [int(x) for x in str(args.prompt_len).split(",")]
    scfg = ServeConfig(
        max_batch=args.max_batch,
        max_len=max(prompt_lens) + args.max_new + 1,
        temperature=args.temperature,
    )
    continuous = args.continuous or args.paged
    if continuous:
        # one process-wide Runtime: the engine leases its calibrated
        # executor width from it per step instead of owning a pool
        import repro
        runtime = repro.Runtime(args.runtime_workers,
                                calibration_path=args.calibration_store,
                                pinning=args.pinning)
        repro.set_default_runtime(runtime)
        if args.paged:
            pcfg = PagedConfig(page_size=args.page_size, n_pages=args.n_pages,
                               prefill_chunk=args.prefill_chunk)
            engine = PagedEngine(cfg, params, scfg, paged=pcfg,
                                 max_executors=args.max_executors,
                                 runtime=runtime,
                                 decode_host_mode=args.decode_host_mode,
                                 schedule_search=args.schedule_search)
            print(f"paged engine: {engine.n_executors} executors leased of "
                  f"{runtime.n_workers}, {engine.capacity} slots, "
                  f"{engine.page_pool.n_pages} pages x {pcfg.page_size} tok, "
                  f"chunk={engine.chunk}, decode={engine.decode_host_mode}")
        else:
            engine = ContinuousEngine(cfg, params, scfg,
                                      max_executors=args.max_executors,
                                      runtime=runtime,
                                      decode_host_mode=args.decode_host_mode,
                                      schedule_search=args.schedule_search)
            print(f"continuous engine: {engine.n_executors} executors leased of "
                  f"{runtime.n_workers} (profiled best {engine.profile.best_config}), "
                  f"{engine.capacity} slots, decode={engine.decode_host_mode}")
    else:
        engine = ServeEngine(cfg, params, scfg)

    if continuous and args.check != "off":
        # verify the engine's captured executables before serving a single
        # request; strict mode refuses to serve over a bad artifact
        import jax.numpy as jnp

        from repro.checks import (Report, cross_graph_hazards, infer_effects,
                                  shared_buffers)

        rep = Report()
        exes = [engine._decode_exe]
        chunk_exe = getattr(engine, "_chunk_exe", None)
        if chunk_exe is not None:
            exes.append(chunk_exe)
        for exe in exes:
            rep.extend(exe.verify(hazards=True))
        if chunk_exe is not None:
            # the decode step and the chunk graph read the engine's one
            # ``_pages`` pool and must not write it — both bind that object,
            # so alias discovery is by array identity over the two bound
            # input maps
            cache_spec = {
                "len": jnp.zeros((engine.capacity,), jnp.int32),
                "table": jnp.full((engine.capacity, engine.n_pt), -1,
                                  jnp.int32),
                "pages": engine._pages,
            }
            tok = jax.ShapeDtypeStruct((engine.capacity, 1), jnp.int32)
            bind_d = engine._decode_exe.captured.bind(
                (params, cache_spec, tok))
            bind_c = chunk_exe.captured.bind(
                (params, engine._pages,
                 jnp.full((engine.n_pt,), -1, jnp.int32),
                 {"tokens": jax.ShapeDtypeStruct((1, engine.chunk),
                                                 jnp.int32)},
                 jnp.int32(0), jnp.int32(engine.chunk)))
            rep.extend(cross_graph_hazards(
                infer_effects(engine._decode_exe.graph),
                infer_effects(chunk_exe.graph),
                shared_buffers(bind_d, bind_c)))
        print(f"check[{args.check}]: {rep.summary()}")
        body = rep.render(min_severity="warning")
        if body != "clean: no findings":
            print(body)
        if args.check == "strict":
            rep.raise_if_errors()

    arrivals = build_requests(cfg, n_requests=args.requests, prompt_lens=prompt_lens,
                              max_new=args.max_new, arrival_rate=args.arrival_rate)
    done, lat, wall = drive(engine, arrivals, continuous=continuous)
    n_tokens = sum(len(r.output) for r in done)
    p50 = percentile(lat.values(), 0.50)
    p95 = percentile(lat.values(), 0.95)
    mode = "paged" if args.paged else ("continuous" if continuous else "wave")
    print(f"[{mode}] served {len(done)} requests, {n_tokens} tokens in {wall:.2f}s "
          f"({n_tokens / wall:.1f} tok/s incl. prefill+compile); "
          f"latency p50={p50 * 1e3:.0f}ms p95={p95 * 1e3:.0f}ms")
    if continuous and args.dump_trace:
        # measured-vs-simulated timeline of the decode graph (paper §5.2)
        print(engine._decode_exe.render_trace(fmt=args.dump_trace))
    if args.paged:
        print("  " + " ".join(f"{k}={v}" for k, v in engine.stats().items()))
        engine.close()
    elif continuous:
        print(f"  steps={engine.n_steps} decode_steps={engine.n_decode_steps} "
              f"overlapped_prefills={engine.n_overlapped_prefills}")
        engine.close()
    bad = [t for r in done for t in r.output if t >= cfg.vocab_size]
    if bad:   # not an assert: the check must survive python -O
        raise SystemExit(f"emitted out-of-vocab ids: {bad[:5]}")
    for r in done[:3]:
        print(f"  req {r.request_id}: {len(r.output)} tokens, first 8 = {r.output[:8]}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
