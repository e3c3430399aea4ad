"""Training CLI: ``python -m repro.launch.train --arch gemma-2b [--smoke]``.

Wires the full stack: config -> synthetic data pipeline -> sharded train
step (jit) -> fault-tolerant Trainer (checkpoint/restart, straggler
watchdog).  ``--smoke`` runs a reduced config; ``--mesh 2x2`` shards the
state and the step over a (data, model) mesh of the local devices.

    PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --smoke \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs.base import ModelConfig, ShapeSpec, get_config
from repro.data import DataConfig, SyntheticTokens
from repro.dist.sharding import (MeshCtx, batch_axes, batch_pspecs, state_pspecs,
                                 use_mesh)
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.optim.adamw import AdamWConfig
from repro.train.step import TrainStepConfig, init_train_state, make_train_step
from repro.train.trainer import Trainer, TrainerConfig


def _named(mesh, specs: Any) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))


def state_shardings(cfg: ModelConfig, adamw: AdamWConfig, mesh) -> Any:
    """Train-state shardings on ``mesh``: the Megatron rules of
    ``state_pspecs`` plus ZeRO-3 over ``data``, so params and both AdamW
    moments are split over every device."""
    shapes = jax.eval_shape(
        lambda k: init_train_state(cfg, k, adamw), jax.random.key(0))
    return _named(mesh, state_pspecs(cfg, shapes, mesh, fsdp=True))


def init_state(cfg: ModelConfig, key, adamw: AdamWConfig, mesh=None) -> dict:
    """The train state; on a mesh it is created already sharded (the init is
    jitted with ``out_shardings``), never whole on one device."""
    if mesh is None:
        return init_train_state(cfg, key, adamw)
    init = jax.jit(lambda k: init_train_state(cfg, k, adamw),
                   out_shardings=state_shardings(cfg, adamw, mesh))
    return init(key)


def make_run_step(cfg: ModelConfig, tcfg: TrainStepConfig, mesh=None, *,
                  global_batch: int, seq_len: int) -> Callable:
    """``run_step(state, batch) -> (state, metrics)``; on a mesh the batch is
    split over its data axes and the model's ``shard`` calls resolve
    against the mesh while the step traces."""
    step = make_train_step(cfg, tcfg)
    if mesh is None:
        return jax.jit(step, donate_argnums=(0,))
    ctx = MeshCtx(mesh, batch_axes(mesh, global_batch))
    batch_spec = jax.ShapeDtypeStruct((global_batch, seq_len), jnp.int32)
    batch_sh = _named(mesh, batch_pspecs(
        {"tokens": batch_spec, "labels": batch_spec}, mesh, global_batch))
    state_sh = state_shardings(cfg, tcfg.adamw, mesh)
    step_jit = jax.jit(step, donate_argnums=(0,),
                       in_shardings=(state_sh, batch_sh),
                       out_shardings=(state_sh, NamedSharding(mesh, P())))

    def run_step(state, batch):
        with use_mesh(ctx):
            return step_jit(state, batch)

    return run_step


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true", help="reduced config (CPU-sized)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--data", default="bigram", choices=("bigram", "uniform", "copy"))
    p.add_argument("--mesh", default=None, help="e.g. '2x4' => data=2, model=4")
    p.add_argument("--no-graphi", action="store_true",
                   help="skip the Graphi capture/schedule of the loss graph")
    p.add_argument("--calibration-store", default=None,
                   help="JSON path backing the process Runtime's calibration "
                        "store (shared with any serve engine in this process)")
    p.add_argument("--schedule-search", choices=("off", "auto", "force"),
                   default="auto",
                   help="simulator-guided schedule search for the Graphi "
                        "loss-graph schedule: 'auto' searches when measured "
                        "costs back the graph, 'force' always, 'off' plain "
                        "CPF")
    p.add_argument("--pinning", choices=("off", "auto", "on"), default="off",
                   help="pin the Runtime's executor threads to disjoint "
                        "core sets (repro.hwperf): 'auto' where supported, "
                        "'on' warns once where it isn't")
    p.add_argument("--dump-trace", choices=("ascii", "csv"), default=None,
                   help="print the Graphi loss graph's execution timeline "
                        "(simulated on this sim-backend path)")
    args = p.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    shape = ShapeSpec("cli", args.seq, args.batch, "train")

    # the process-wide Runtime: the Graphi view of the loss graph compiles
    # through it (shared schedule caches + persistent calibration), and any
    # host-backend execution in this process leases its executors
    import repro
    runtime = repro.Runtime(calibration_path=args.calibration_store,
                            pinning=args.pinning)
    repro.set_default_runtime(runtime)
    scheduled_makespan = None
    if not args.no_graphi:
        from repro.train.step import compile_lm_loss

        exe = compile_lm_loss(cfg, shape, backend="sim", runtime=runtime,
                              schedule_search=args.schedule_search)
        scheduled_makespan = exe.schedule.makespan
        print(f"graphi: loss graph {len(exe.graph)} nodes, width "
              f"{exe.graph.width()}, {exe.schedule.n_executors}x"
              f"{exe.schedule.team_size} executors ({exe.schedule.policy}), "
              f"scheduled makespan "
              f"{scheduled_makespan * 1e3:.2f} ms ({runtime.describe()})")
        if args.dump_trace:
            print(exe.render_trace(fmt=args.dump_trace))

    tcfg = TrainStepConfig(
        microbatches=args.microbatches,
        remat=not args.smoke,
        adamw=AdamWConfig(lr=args.lr),
        total_steps=args.steps,
        warmup_steps=max(1, args.steps // 20),
    )
    mesh = None
    if args.mesh:
        dims = tuple(int(d) for d in args.mesh.split("x"))
        mesh = make_mesh(dims, ("data", "model")[: len(dims)])
    state = init_state(cfg, jax.random.key(0), tcfg.adamw, mesh)
    run_step = make_run_step(cfg, tcfg, mesh, global_batch=args.batch,
                             seq_len=args.seq)

    data = SyntheticTokens(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch, kind=args.data,
    ))

    ckpt = CheckpointManager(args.ckpt_dir, keep=3) if args.ckpt_dir else None
    trainer = Trainer(
        run_step, state, data.batch,
        TrainerConfig(
            total_steps=args.steps,
            checkpoint_every=args.ckpt_every,
            log_every=args.log_every,
        ),
        checkpoint=ckpt,
        scheduled_makespan=scheduled_makespan,
    )
    report = trainer.run()
    for rec in report.history:
        if "loss" in rec:
            print(f"step {rec['step']:6d}  loss {rec['loss']:.4f}  "
                  f"({rec['time_s']*1e3:.0f} ms/step)")
    print(f"done: {report.steps_run} steps, {report.restarts} restarts, "
          f"final loss {report.final_loss:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
