"""``python -m repro.checks`` — the static-verification sweep CI runs.

Default run: source rules (W-ASSERT) plus a live segment-FIFO probe (two
static plans replayed concurrently on one pool, journal replayed through
E-FIFO).  ``--zoo`` adds the config-zoo model sweep: for every arch, capture
the lm_loss, prefill, and decode graphs (plus the paged decode /
chunk-prefill pair where supported), then run every structural checker and
the hazard analysis over graph, schedule, and compiled host plan.  Exit
status 1 when any error-severity finding survives.
"""
from __future__ import annotations

import argparse
import threading
from typing import Any

from .assertscan import scan_asserts
from .effects import infer_effects, shared_buffers
from .hazards import check_hazards, cross_graph_hazards
from .invariants import check_segment_fifo, segment_queues
from .report import Report
from . import verify_all

__all__ = ["main", "run_fifo_probe", "run_zoo_arch"]

# zoo capture shape — small enough that ten archs sweep in CI minutes,
# deep enough (2 smoke layers, real vocab padding) that fusion, scan
# bodies, and cache scatters all appear in the captured graphs
_B, _SEQ, _MAX_LEN, _PAGE = 2, 16, 32, 8
_N_WORKERS = 8


def run_fifo_probe(*, runs: int = 6) -> Report:
    """Replay two static plans concurrently on one journaled pool and verify
    segment-submission FIFO consistency from the evidence."""
    import repro
    from repro.core.engine import ExecutorPool
    from repro.core.static_host import layered_graph

    g = layered_graph(3, 2)
    exe = repro.compile(g, n_workers=4, n_executors=2, team_size=2)
    plan = exe.host_plan(2)
    pool = ExecutorPool(2)
    pool.segment_log = []
    try:
        def worker() -> None:
            for _ in range(runs):
                plan.run({"x": 1.0}, pool=pool)

        threads = [threading.Thread(target=worker) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        pool.close()
    return check_segment_fifo(segment_queues(pool.segment_log))


def _check_executable(exe: Any, label: str) -> Report:
    rep = verify_all(exe.graph, exe.schedule, exe.host_plan())
    return rep.scoped(label)


def run_zoo_arch(arch: str) -> Report:
    """Capture and verify one arch's graphs (lm_loss, prefill, decode, and
    the paged pair where :func:`~repro.models.transformer.paged_supported`)."""
    import jax
    import jax.numpy as jnp

    import repro
    from repro.configs.base import ShapeSpec, get_config
    from repro.models import api as model_api
    from repro.models import transformer
    from repro.serve.step import (make_decode_step, make_paged_decode_step,
                                  make_prefill_chunk_step, make_prefill_step)
    from repro.train.step import compile_lm_loss

    rep = Report()
    cfg = get_config(arch, smoke=True)
    shape = ShapeSpec("check", _SEQ, _B, "train")
    key = jax.random.key(0)
    params = transformer.init_params(cfg, key)

    def guarded(label: str, build) -> None:
        try:
            rep.extend(build())
        except Exception as e:  # noqa: BLE001 — one bad graph must not hide the rest
            rep.add("Z-SKIP", "warning",
                    f"{type(e).__name__}: {e}", where=f"{arch}/{label}")

    def loss() -> Report:
        exe = compile_lm_loss(cfg, shape, backend="host",
                              n_workers=_N_WORKERS)
        return _check_executable(exe, f"{arch}/lm_loss")

    def prefill() -> Report:
        cache = transformer.init_cache(cfg, _B, _MAX_LEN)
        batch = model_api.input_specs(cfg, shape, kind="prefill")
        exe = repro.compile(make_prefill_step(cfg), params, cache, batch,
                            n_workers=_N_WORKERS,
                            name=f"{arch}.prefill")
        return _check_executable(exe, f"{arch}/prefill")

    def decode() -> Report:
        cache = transformer.init_cache(cfg, _B, _MAX_LEN)
        tok = jax.ShapeDtypeStruct((_B, 1), jnp.int32)
        exe = repro.compile(make_decode_step(cfg), params, cache, tok,
                            n_workers=_N_WORKERS,
                            name=f"{arch}.decode")
        return _check_executable(exe, f"{arch}/decode")

    guarded("lm_loss", loss)
    guarded("prefill", prefill)
    guarded("decode", decode)

    if transformer.paged_supported(cfg):
        def paged() -> Report:
            sub = Report()
            n_pt = _MAX_LEN // _PAGE
            pcache = transformer.init_paged_cache(
                cfg, _B, _MAX_LEN, n_pages=_B * n_pt, page_size=_PAGE)
            pages = pcache["pages"]   # ONE pool object for both graphs
            cache_spec = {"len": jnp.zeros((_B,), jnp.int32),
                          "table": jnp.full((_B, n_pt), -1, jnp.int32),
                          "pages": pages}
            tok = jnp.zeros((_B, 1), jnp.int32)
            dec = repro.compile(
                make_paged_decode_step(cfg), params, cache_spec, tok,
                n_workers=_N_WORKERS, name=f"{arch}.paged_decode")
            row = jnp.full((n_pt,), -1, jnp.int32)
            chunk_batch = {"tokens": jnp.zeros((1, _PAGE), jnp.int32)}
            start, valid = jnp.int32(0), jnp.int32(_PAGE)
            chunk = repro.compile(
                make_prefill_chunk_step(cfg, _PAGE), params, pages, row,
                chunk_batch, start, valid,
                n_workers=_N_WORKERS, name=f"{arch}.prefill_chunk")
            sub.extend(_check_executable(dec, f"{arch}/paged_decode"))
            sub.extend(_check_executable(chunk, f"{arch}/prefill_chunk"))

            # cross-graph: both graphs read the one pool, concurrently, and
            # neither may write it — the engine writes it only after both
            # are dispatched.  Certified here, not assumed
            eff_d = infer_effects(dec.graph)
            eff_c = infer_effects(chunk.graph)
            bind_d = dec.captured.bind((params, cache_spec, tok))
            bind_c = chunk.captured.bind(
                (params, pages, row, chunk_batch, start, valid))
            shared = shared_buffers(bind_d, bind_c)
            pool_leaves = {id(x) for x in jax.tree.leaves(pages)}
            pool_shared = [
                (a, b) for a, b in shared if id(bind_d[a]) in pool_leaves]
            if not pool_shared:
                sub.add("H-XWW", "error",
                        "paged decode and prefill chunk share no pool "
                        "buffers — alias discovery broke",
                        where=f"{arch}/paged")
            sub.extend(cross_graph_hazards(eff_d, eff_c, shared))
            for label, eff, bufs in (
                    ("paged decode", eff_d, [a for a, _ in pool_shared]),
                    ("prefill chunk", eff_c, [b for _, b in pool_shared])):
                if eff.read_only(bufs):
                    sub.add("C-RO", "info",
                            f"{label} certified read-only over "
                            f"{len(bufs)} shared pool buffer(s)",
                            where=f"{arch}/paged")
                else:
                    sub.add("H-XWW", "error",
                            f"{label} writes a pool buffer — the pool "
                            "must change only through the engine's "
                            "donated installs",
                            where=f"{arch}/paged")
            return sub

        guarded("paged", paged)
    return rep


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.checks",
        description="static verifier: structural invariants, effect/hazard "
                    "analysis, source rules",
    )
    ap.add_argument("--zoo", action="store_true",
                    help="capture and verify the config-zoo model graphs")
    ap.add_argument("--arch", action="append", default=None,
                    help="restrict --zoo to this arch (repeatable)")
    ap.add_argument("--no-asserts", action="store_true",
                    help="skip the W-ASSERT source scan")
    ap.add_argument("--no-fifo", action="store_true",
                    help="skip the live segment-FIFO probe")
    ap.add_argument("-v", "--verbose", action="store_true",
                    help="show info-severity findings")
    args = ap.parse_args(argv)

    total = Report()
    if not args.no_asserts:
        asserts = scan_asserts()
        total.extend(asserts)
        print(f"asserts : {asserts.summary()}")
    if not args.no_fifo:
        fifo = run_fifo_probe()
        total.extend(fifo)
        print(f"fifo    : {fifo.summary()}")
    if args.zoo or args.arch:
        from repro.configs.base import list_archs

        archs = args.arch or list_archs()
        for arch in archs:
            rep = run_zoo_arch(arch)
            total.extend(rep)
            print(f"{arch:22s}: {rep.summary()}")

    min_sev = "info" if args.verbose else "warning"
    body = total.render(min_severity=min_sev)
    if body != "clean: no findings" or args.verbose:
        print()
        print(body)
    print()
    print(f"TOTAL   : {total.summary()}")
    return 0 if total.ok else 1
