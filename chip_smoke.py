#!/usr/bin/env python3
"""Smoke test of the system's main paths on a TPU v5e, through the entry
points a user calls.  Weights and traffic are random, made from ``--seed``.

    python chip_smoke.py               # paged serving, one chip
    python chip_smoke.py --four-chip   # sharded training, four chips

Default phase — paged serving of h2o-danube3-4b at published widths (24
layers, d_model 3840, 32 query / 8 KV heads of 120, vocab 32 000, window
4096; about 4.0 B params, 7.9 GB in bf16).  A ``PagedEngine`` is built the
way ``repro.launch.serve`` builds it (``Runtime`` -> ``api.compile`` ->
``calibrate`` -> static decode plan) and serves 8 requests (prompts of
64/192/512 tokens, 32 new tokens each, pages of 16, prefill chunks of 64).
It fails unless every request returns exactly 32 in-vocab tokens, the
compiled decode node that holds the paged attention contains the Pallas
kernel's ``tpu_custom_call``, and the first 8 tokens of two requests equal
greedy decoding with a plain ``jax.jit`` forward on the same chip (a
differing token passes only as a near-tie: see ``LOGIT_TIE_TOL``).

``--four-chip`` — training of gemma-2b at published widths (18 layers,
d_model 2048, 8 query / 1 KV head of 256, d_ff 16 384, vocab 256 000; about
2.5 B params) on a 2x2 (data, model) mesh in one process, through
``repro.launch.train``'s sharded init and step.  The global batch is 8 x 512
tokens: the train state (bf16 params, fp32 AdamW moments, about 25 GB) is
split over all four chips, and the step fits one v5e's 16 GB.  Five steps;
it fails on a non-finite loss, a trainer restart, a step-0 loss off the
single-chip forward-only reference by more than ``LOSS_RTOL``, or a device
holding more than ``MEM_BALANCE`` x the mean bytes in use after the first
step.

Without a TPU it exits non-zero at once and prints no result.  The last
line of standard output is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro  # noqa: E402
from repro.configs.base import get_config  # noqa: E402
from repro.data import DataConfig, SyntheticTokens  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.serve import build_requests, drive  # noqa: E402
from repro.launch.train import init_state, make_run_step  # noqa: E402
from repro.models import transformer  # noqa: E402
from repro.models.api import lm_loss  # noqa: E402
from repro.optim.adamw import AdamWConfig  # noqa: E402
from repro.serve.engine import ServeConfig  # noqa: E402
from repro.serve.paged import PagedConfig, PagedEngine  # noqa: E402
from repro.serve.step import mask_pad_vocab  # noqa: E402
from repro.train.step import TrainStepConfig  # noqa: E402
from repro.train.trainer import Trainer, TrainerConfig  # noqa: E402

# -- serving phase -------------------------------------------------------------
SERVE_ARCH = "h2o-danube3-4b"
N_REQUESTS = 8
PROMPT_LENS = (64, 192, 512)
MAX_NEW = 32
PAGE_SIZE = 16
PREFILL_CHUNK = 64
N_REF_REQUESTS = 2            # requests checked against the plain forward
N_REF_TOKENS = 8              # ... on their first tokens
# a token that differs from the reference's greedy choice still passes as a
# near-tie when the reference's logit for it is below the top one by at most
# this fraction of the position's largest |logit|: bf16 keeps 8 bits, and the
# kernel's online softmax rounds differently from the forward's softmax over
# 24 layers.  The first such position ends the comparison (the streams part)
LOGIT_TIE_TOL = 2.0 ** -5

# -- training phase ------------------------------------------------------------
TRAIN_ARCH = "gemma-2b"
TRAIN_MESH = (2, 2)           # (data, model)
GLOBAL_BATCH = 8
SEQ_LEN = 512
N_STEPS = 5
# step-0 loss vs the single-chip reference: bf16 matmuls partitioned over
# the mesh sum in another order; 5e-3 is a little over one bf16 ulp (2^-8)
LOSS_RTOL = 5e-3
MEM_BALANCE = 1.5             # max bytes in use over the mean, per device


def _holds_kernel(eqns) -> bool:
    """Whether a Pallas call is among ``eqns`` or their sub-jaxprs."""
    for e in eqns:
        if e.primitive.name == "pallas_call":
            return True
        for p in e.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns") and _holds_kernel(sub.eqns):
                    return True
    return False


def _node_hlo(graph, name: str) -> str:
    """Compiled text of a jitted graph node, lowered on its input shapes."""
    node = graph[name]
    by_dep = {dep: (var, n_slots)
              for var, dep, _, n_slots in node.meta["_imports"]}
    args = []
    for i, dep in enumerate(node.deps):
        var, n_slots = by_dep[i]
        if n_slots == 1:
            args.append(jax.ShapeDtypeStruct(var.aval.shape, var.aval.dtype))
        else:
            args.append(tuple(jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
                              for v in graph[dep].meta["_exports"]))
    return node.fn.lower(*args).compile().as_text()


def greedy_reference(cfg, params, prompts, n_tokens: int):
    """Greedy tokens and their logits from a plain jitted forward over the
    growing sequence.  One right-padded buffer serves every prompt: the
    attention is causal, so positions past the one read cannot change it."""
    width = max(len(p) for p in prompts) + n_tokens
    fwd = jax.jit(lambda p, t: mask_pad_vocab(
        transformer.forward(cfg, p, {"tokens": t})[0], cfg.vocab_size))
    out = []
    for prompt in prompts:
        buf = np.zeros((1, width), np.int32)
        buf[0, :len(prompt)] = prompt
        toks, logits = [], []
        for i in range(n_tokens):
            pos = len(prompt) + i - 1
            lg = np.asarray(fwd(params, jnp.asarray(buf))[0, pos])
            toks.append(int(lg.argmax()))
            logits.append(lg)
            buf[0, pos + 1] = toks[-1]
        out.append((toks, logits))
    return out


def serve_phase(cfg, *, seed: int) -> dict:
    """Serve the traffic above on a freshly built ``PagedEngine``."""
    # jitted as in repro.launch.serve: the weights are made in place
    params = jax.jit(transformer.init_params, static_argnums=0)(
        cfg, jax.random.key(seed))
    jax.block_until_ready(params)
    scfg = ServeConfig(max_batch=N_REQUESTS,
                       max_len=max(PROMPT_LENS) + MAX_NEW + 1)
    runtime = repro.Runtime()
    repro.set_default_runtime(runtime)
    t0 = time.perf_counter()
    engine = PagedEngine(cfg, params, scfg,
                         paged=PagedConfig(page_size=PAGE_SIZE,
                                           prefill_chunk=PREFILL_CHUNK),
                         runtime=runtime, decode_host_mode="static",
                         schedule_search="auto")
    setup_s = time.perf_counter() - t0
    arrivals = build_requests(cfg, n_requests=N_REQUESTS,
                              prompt_lens=list(PROMPT_LENS), max_new=MAX_NEW,
                              seed=seed)
    done, _, wall = drive(engine, arrivals, continuous=True)
    stats = engine.stats()
    engine.close()

    graph = engine._decode_exe.graph
    kernel_nodes = [n for n in graph.names
                    if graph[n].meta and _holds_kernel(graph[n].meta["_eqns"])]
    kernel_hlo = {n: _node_hlo(graph, n) for n in kernel_nodes}
    t0 = time.perf_counter()
    ref = greedy_reference(cfg, params,
                           [r.prompt for r in done[:N_REF_REQUESTS]],
                           N_REF_TOKENS)
    ref_s = time.perf_counter() - t0
    runtime.close()
    return {"outputs": [list(r.output) for r in done], "reference": ref,
            "kernel_hlo": kernel_hlo, "setup_s": setup_s, "serve_s": wall,
            "reference_s": ref_s, "stats": stats}


def serve_failures(res: dict, cfg) -> list[str]:
    """Every check of the serving phase that did not hold."""
    bad = []
    outs = res["outputs"]
    if len(outs) != N_REQUESTS:
        bad.append(f"{len(outs)} of {N_REQUESTS} requests returned")
    for i, toks in enumerate(outs):
        if len(toks) != MAX_NEW:
            bad.append(f"request {i}: {len(toks)} tokens, want {MAX_NEW}")
        if any(not 0 <= t < cfg.vocab_size for t in toks):
            bad.append(f"request {i}: token outside the vocab")
    if not res["kernel_hlo"]:
        bad.append("no decode node holds a Pallas call")
    for name, text in res["kernel_hlo"].items():
        if "tpu_custom_call" not in text:
            bad.append(f"decode node {name} compiled without tpu_custom_call")
    for i, (toks, logits) in enumerate(res["reference"]):
        for j, (got, want) in enumerate(zip(outs[i], toks)):
            if got == want:
                continue
            lg = logits[j]
            gap = float(lg[want] - lg[got])
            tol = LOGIT_TIE_TOL * float(np.abs(lg[np.isfinite(lg)]).max())
            if not gap <= tol:
                bad.append(f"request {i} token {j}: {got} != reference {want} "
                           f"(logit gap {gap:.4g} > {tol:.4g})")
            break
    return bad


def _bytes_in_use(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else int(stats["bytes_in_use"])


def reference_loss(cfg, params, batch: dict, device) -> float:
    """Forward-only ``lm_loss`` of ``params`` on one chip, row by row (the
    full batch's fp32 logits would not fit beside the params).  Every label
    is a real token, so the mean of the row means is the batch mean."""
    whole = jax.device_put(params, device)
    loss = jax.jit(lambda p, b: lm_loss(cfg, p, b)[0])
    rows = [float(loss(whole, {k: jnp.asarray(v[i:i + 1], device=device)
                               for k, v in batch.items()}))
            for i in range(GLOBAL_BATCH)]
    del whole
    return float(np.mean(rows))


def train_phase(cfg, mesh, *, seed: int) -> dict:
    """Five sharded train steps of ``cfg`` on ``mesh`` under the Trainer."""
    tcfg = TrainStepConfig(remat=True, adamw=AdamWConfig(lr=3e-4),
                           total_steps=N_STEPS, warmup_steps=1)
    t0 = time.perf_counter()
    state = init_state(cfg, jax.random.key(seed), tcfg.adamw, mesh)
    jax.block_until_ready(state)
    init_s = time.perf_counter() - t0
    data = SyntheticTokens(DataConfig(vocab_size=cfg.vocab_size,
                                      seq_len=SEQ_LEN,
                                      global_batch=GLOBAL_BATCH, seed=seed))
    devices = list(mesh.devices.flat)
    ref = reference_loss(cfg, state["params"], data.batch(0), devices[0])
    run_step = make_run_step(cfg, tcfg, mesh, global_batch=GLOBAL_BATCH,
                             seq_len=SEQ_LEN)
    mem: list = []

    def step_and_record(state, batch):
        out = run_step(state, batch)
        if not mem:     # device memory after the first step
            jax.block_until_ready(out)
            mem.extend(_bytes_in_use(d) for d in devices)
        return out

    report = Trainer(step_and_record, state, data.batch,
                     TrainerConfig(total_steps=N_STEPS, log_every=1)).run()
    losses = [rec["loss"] for rec in report.history if "loss" in rec]
    return {"losses": losses, "reference_loss": ref,
            "restarts": report.restarts, "bytes_in_use": mem,
            "init_s": init_s,
            "step_s": [rec["time_s"] for rec in report.history
                       if "time_s" in rec]}


def train_failures(res: dict) -> list[str]:
    """Every check of the training phase that did not hold."""
    bad = []
    losses = res["losses"]
    if len(losses) != N_STEPS:
        bad.append(f"{len(losses)} of {N_STEPS} steps logged a loss")
    if not all(np.isfinite(losses)):
        bad.append(f"non-finite loss: {losses}")
    if res["restarts"]:
        bad.append(f"{res['restarts']} trainer restarts")
    if losses:
        rel = abs(losses[0] - res["reference_loss"]) / abs(res["reference_loss"])
        if not rel <= LOSS_RTOL:
            bad.append(f"step-0 loss {losses[0]:.6f} vs single-chip reference "
                       f"{res['reference_loss']:.6f}: rel diff {rel:.3g} > "
                       f"{LOSS_RTOL}")
    mem = res["bytes_in_use"]
    if not mem or None in mem:
        bad.append("a device reports no memory stats")
    elif max(mem) > MEM_BALANCE * float(np.mean(mem)):
        bad.append(f"bytes in use {mem} exceed {MEM_BALANCE}x their mean")
    return bad


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--four-chip", action="store_true",
                   help="sharded gemma-2b training on a 2x2 mesh, and nothing else")
    p.add_argument("--seed", type=int, default=0,
                   help="seed of the random weights and traffic")
    args = p.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}")

    if args.four_chip:
        if len(jax.devices()) < 4:
            print(f"chip_smoke: --four-chip needs 4 chips, found "
                  f"{len(jax.devices())}", file=sys.stderr)
            return 1
        mesh = make_mesh(TRAIN_MESH, ("data", "model"),
                         devices=jax.devices()[:4])
        res = train_phase(get_config(TRAIN_ARCH), mesh, seed=args.seed)
        print(f"train: sharded state init {res['init_s']:.2f} s (set-up); "
              f"step times {[round(t, 3) for t in res['step_s']]} s "
              f"(step 1 includes compile)")
        print(f"train: losses {res['losses']}; single-chip reference "
              f"{res['reference_loss']:.6f}; restarts {res['restarts']}")
        print(f"train: bytes in use after step 1, per device: "
              f"{res['bytes_in_use']}")
        bad = train_failures(res)
    else:
        cfg = get_config(SERVE_ARCH)
        res = serve_phase(cfg, seed=args.seed)
        print(f"serve: engine set-up {res['setup_s']:.2f} s (capture, "
              f"calibrate, static plan, warm-up compiles)")
        print(f"serve: {N_REQUESTS} requests in {res['serve_s']:.2f} s "
              f"(one run, first-call compiles included); {res['stats']}")
        print(f"serve: kernel decode nodes {sorted(res['kernel_hlo'])}; "
              f"reference forward {res['reference_s']:.2f} s (incl. compile)")
        for i, (toks, _) in enumerate(res["reference"]):
            print(f"serve: request {i} engine {res['outputs'][i][:N_REF_TOKENS]}"
                  f" reference {toks}")
        bad = serve_failures(res, cfg)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    print(f"peak_bytes_in_use per device: {peaks}")
    for msg in bad:
        print(f"FAIL {msg}", file=sys.stderr)
    if bad:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
