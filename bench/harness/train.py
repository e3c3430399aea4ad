"""Training cells: the sharded step of ``repro.launch.train`` on a mesh of
the cell's chips, fed as ``launch/train.py`` feeds it.

Set-up makes the weights on the device from the seed, already split by the
program's own state shardings, builds ``make_run_step``, and drives that
same step object through its first ``CHECK_STEPS`` steps on the batches of
steps 0 and 1.  Their losses, the first gradient as the optimizer got it
(read back from the first moment) and the parameters' change over the two
are what the check compares.  The window then runs steps back to back until
``--seconds`` have passed, and ends when the last step it started completes.

The reference follows two steps, not three: with the program's state gone
it holds the weights, the first clipped gradient and the second gradient on
the chips, which is as much as fits beside the activations; the moments of
step 2 follow from those two gradients.
"""
from __future__ import annotations

import gc
import math
import time
from dataclasses import dataclass, field

from . import flops, trace
from .traffic import seed_words

CHECK_STEPS = 2
TINY = 1e-3        # leaves whose reference gradient is below this share of
                   # the median leaf's move by round-off alone: not compared


@dataclass
class TrainRecord:
    kind: str = "train"
    setup_s: float = 0.0
    t0: float = 0.0
    t1: float = 0.0
    step_ends: list = field(default_factory=list)      # host clock, window
    tokens_per_step: int = 0
    losses: list = field(default_factory=list)         # window steps
    trace: object = None
    model: dict = field(default_factory=dict)
    seq_len: int = 0
    n_chips: int = 1
    compiles_in_window: int = 0


def _diff_norms(a, b):
    import jax
    import jax.numpy as jnp

    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                        - y.astype(jnp.float32))))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


def run(cell, seed: int, seconds: float, traced: bool, devices,
        t_start: float, state_dir, log):
    import jax
    import jax.numpy as jnp

    from repro.data import DataConfig, SyntheticTokens
    from repro.launch.mesh import make_mesh
    from repro.launch.train import make_run_step, state_shardings
    from repro.optim.adamw import AdamWConfig
    from repro.train.step import TrainStepConfig

    from bench.reference import adamw_ref

    from . import weights
    from .serve import CompileCounter, model_config

    m, t = cell.config, cell.traffic
    opt = dict(t["optimizer"])
    B, S = int(t["global_batch"]), int(t["seq_len"])
    rec = TrainRecord(model=m, seq_len=S, tokens_per_step=B * S,
                      n_chips=len(devices))
    cfg = model_config(m)
    mesh = make_mesh(tuple(t["mesh"]), ("data", "model"), devices=devices)
    adamw = AdamWConfig(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
                        eps=opt["eps"], weight_decay=opt["weight_decay"],
                        clip_norm=opt["clip_norm"])
    tcfg = TrainStepConfig(remat=True, adamw=adamw,
                           warmup_steps=opt["warmup_steps"],
                           total_steps=opt["total_steps"])
    sh = state_shardings(cfg, adamw, mesh)
    w_weights, w_data = seed_words(seed, 2)
    key = jax.random.key(w_weights)
    params = weights.make(m, key, out_shardings=sh["params"])
    zeros = jax.jit(lambda p: jax.tree.map(
        lambda a: jnp.zeros(a.shape, jnp.float32), p), out_shardings=sh["m"])
    state = {"params": params, "m": zeros(params), "v": zeros(params),
             "step": jax.device_put(jnp.zeros((), jnp.int32), sh["step"])}
    del params
    run_step = make_run_step(cfg, tcfg, mesh, global_batch=B, seq_len=S)
    data = SyntheticTokens(DataConfig(vocab_size=m["vocab_size"], seq_len=S,
                                      global_batch=B, seed=w_data,
                                      kind=t["data"]))
    norms = jax.jit(adamw_ref.leaf_norms)
    diff = jax.jit(_diff_norms)

    # set-up: the first steps, through the window's own call and feed
    losses, g_norms = [], None
    for i in range(CHECK_STEPS):
        state, met = run_step(state, data.batch(i))
        losses.append(float(met["loss"]))
        if i == 0:
            g_norms = [float(x) / (1 - opt["b1"]) for x in norms(state["m"])]
    p0 = weights.make(m, key, out_shardings=sh["params"])
    d_norms = [float(x) for x in diff(state["params"], p0)]
    del p0
    log(f"set-up steps: losses {losses}")

    counter = CompileCounter()
    log_dir = str(state_dir / "trace")
    counter.on = True
    if traced:
        trace.start(log_dir)
    rec.t0 = time.perf_counter()
    rec.setup_s = rec.t0 - t_start
    step, pending = CHECK_STEPS, []
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            with jax.profiler.TraceAnnotation("bench.step"):
                state, met = run_step(state, data.batch(step))
            step += 1
            pending.append(met["loss"])
            if len(pending) > 1:             # keep one step queued behind
                rec.losses.append(float(pending.pop(0)))
                rec.step_ends.append(time.perf_counter())
            if time.perf_counter() - rec.t0 >= seconds:
                break
        rec.losses.append(float(pending.pop(0)))
        rec.step_ends.append(time.perf_counter())
    rec.t1 = rec.step_ends[-1]
    if traced:
        ev = trace.stop_and_load(log_dir)
        (lo, dur), = [(t0, d) for n, t0, d, _ in ev.host if n == "bench.window"]
        rec.trace = trace.reduce(ev, lo, lo + dur)
    counter.on = False
    rec.compiles_in_window = counter.n
    log(f"window: {len(rec.step_ends)} steps, compilations in window "
        f"{rec.compiles_in_window}, losses {rec.losses[0]} .. {rec.losses[-1]}")
    del state, met, pending, run_step
    gc.collect()
    return rec, {"losses": losses, "g_norms": g_norms, "d_norms": d_norms,
                 "key": key, "devices": devices, "opt": opt,
                 "batches": [data.batch(i) for i in range(CHECK_STEPS)]}


def counts(rec: TrainRecord) -> tuple[int, int]:
    return (len(rec.losses),
            sum(1 for x in rec.losses if not math.isfinite(x)))


def window_tokens_per_s(rec: TrainRecord) -> float:
    return len(rec.step_ends) * rec.tokens_per_step / (rec.t1 - rec.t0)


def mfu(rec: TrainRecord, peaks) -> float:
    per_token = flops.train_token_flops(rec.model, rec.seq_len)
    return (window_tokens_per_s(rec) * per_token
            / (rec.n_chips * peaks.flops))


# -- the reference ------------------------------------------------------------
def reference(m: dict, opt: dict, key, batches, devices, mode: str = "f32",
              rows: slice = slice(None)):
    """The reference's readings for the same weights and batches: losses,
    the first clipped gradient's leaf norms, the change's leaf norms.
    ``rows`` keeps only some rows of every batch (a fault to plant)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bench.reference import adamw_ref

    from . import weights
    from .spec import architecture

    n = len(devices)
    mesh = Mesh(np.array(devices), ("d",))
    rep = NamedSharding(mesh, P())
    by_row = NamedSharding(mesh, P("d"))

    def split(shape):
        dims = sorted(range(len(shape)), key=lambda i: -shape[i])
        for i in dims:
            if shape[i] % n == 0:
                return NamedSharding(mesh, P(*([None] * i + ["d"])))
        return rep

    p_sh = jax.tree.map(lambda s: split(s.shape), weights.specs(m))
    rows_sh = by_row if len(batches[0]["tokens"][rows]) % n == 0 else rep
    hooks = (lambda lp: jax.tree.map(
                 lambda a: jax.lax.with_sharding_constraint(a, rep), lp),
             lambda x: jax.lax.with_sharding_constraint(x, rows_sh))
    arch = architecture(m)
    grad = jax.jit(lambda p, tok, lab: adamw_ref.grads(
        arch, m, p, tok, lab, mode=mode, hooks=hooks),
        out_shardings=(rep, p_sh))
    b1, b2 = opt["b1"], opt["b2"]

    def first(p, g):
        g, _ = adamw_ref.clip(opt, g)
        mom = jax.tree.map(lambda x: (1 - b1) * x, g)
        vel = jax.tree.map(lambda x: (1 - b2) * x * x, g)
        return adamw_ref.apply(opt, p, mom, vel, 1), g

    def second(p, g1, g2):
        g2, _ = adamw_ref.clip(opt, g2)
        mom = jax.tree.map(lambda a, x: b1 * (1 - b1) * a + (1 - b1) * x, g1, g2)
        vel = jax.tree.map(lambda a, x: b2 * (1 - b2) * a * a + (1 - b2) * x * x,
                           g1, g2)
        return adamw_ref.apply(opt, p, mom, vel, 2)

    first = jax.jit(first, out_shardings=(p_sh, p_sh))
    second = jax.jit(second, out_shardings=p_sh)
    norms, diff = jax.jit(adamw_ref.leaf_norms), jax.jit(_diff_norms)

    def put(b):
        return (jax.device_put(jnp.asarray(b["tokens"][rows]), rows_sh),
                jax.device_put(jnp.asarray(b["labels"][rows]), rows_sh))

    p = weights.make(m, key, out_shardings=p_sh)
    loss1, g = grad(p, *put(batches[0]))
    p, g1 = first(p, g)
    del g
    g_norms = [float(x) for x in norms(g1)]
    loss2, g = grad(p, *put(batches[1]))
    p = second(p, g1, g)
    del g, g1
    p0 = weights.make(m, key, out_shardings=p_sh)
    d_norms = [float(x) for x in diff(p, p0)]
    del p, p0
    return {"losses": [float(loss1), float(loss2)], "g_norms": g_norms,
            "d_norms": d_norms}


def leaf_gap(prog, ref) -> float:
    """The worst leaf's gap between the program's norm and the reference's,
    over the larger of that leaf's reference norm and the median leaf's;
    NaN where any norm is."""
    import numpy as np

    prog, ref = np.asarray(prog, float), np.asarray(ref, float)
    return float(np.max(np.abs(prog - ref) / np.maximum(ref, np.median(ref))))


def compare(prog: dict, ref: dict) -> dict:
    """The numbers ``correct`` compares, from two sets of readings.  A
    non-finite reading on either side makes the number NaN, which fails."""
    import numpy as np

    g_ref = np.asarray(ref["g_norms"], float)
    keep = g_ref >= TINY * np.median(g_ref)
    losses = np.asarray(prog["losses"], float), np.asarray(ref["losses"], float)
    return {
        "loss_rel_gap": float(np.max(np.abs(losses[0] - losses[1])
                                     / np.abs(losses[1]))),
        "grad_norm_gap": leaf_gap(np.asarray(prog["g_norms"], float)[keep],
                                  g_ref[keep]),
        "update_norm_gap": leaf_gap(np.asarray(prog["d_norms"], float)[keep],
                                    np.asarray(ref["d_norms"], float)[keep]),
    }


def readings(cell, state: dict) -> dict:
    ref = reference(cell.config, state["opt"], state["key"], state["batches"],
                    state["devices"])
    return compare(state, ref)
