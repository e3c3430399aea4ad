"""Cell builder: (architecture x input-shape x mesh) -> lowerable jit fn.

A *cell* is one entry of the 40-cell dry-run matrix. ``build_cell`` returns
everything needed to ``.lower().compile()`` it with ShapeDtypeStruct inputs —
no device allocation ever happens here.

Per-cell execution plans (microbatching, sequence-parallel activations)
live in ``plan_for``; the perf pass overrides them via ``PlanOverrides``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from typing import Any, Callable

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, ModelConfig, ShapeSpec, get_config
from repro.dist.sharding import (
    MeshCtx,
    batch_axes,
    batch_pspecs,
    cache_pspecs,
    param_pspecs,
    state_pspecs,
    use_mesh,
)
from repro.models import api as model_api
from repro.models import transformer
from repro.serve.step import make_decode_step, make_prefill_step
from repro.train.step import TrainStepConfig, init_train_state, make_train_step

__all__ = ["CellPlan", "Cell", "plan_for", "build_cell", "cell_matrix", "skip_reason"]

# archs with blocks that attend over the full context: long_500k (524k
# decode) is quadratic-cost / unbounded-cache for them -> skipped, per the
# assignment ("skip for pure full-attention archs"); mellum2's every fourth
# layer is full, so its stacked cache would hold 524k positions per layer
FULL_ATTENTION_ARCHS = {
    "gemma-2b",
    "yi-9b",
    "command-r-plus-104b",
    "llava-next-34b",
    "olmoe-1b-7b",
    "granite-moe-1b-a400m",
    "whisper-medium",
    "mellum2-12b-a2-5b",
}


@dataclass(frozen=True)
class CellPlan:
    """Tunable execution plan for one cell (the perf-pass knobs)."""

    microbatches: int = 1
    seq_shard: bool = False       # Megatron-SP residual-stream seq sharding
    remat: bool = True
    donate: bool = True
    fsdp: bool = False            # ZeRO-3 param/moment sharding over 'data'
    extra: dict = field(default_factory=dict)

    def override(self, **kw: Any) -> "CellPlan":
        return replace(self, **kw)


def _pow2_at_least(x: float) -> int:
    return 1 << max(0, math.ceil(math.log2(max(x, 1.0))))


def plan_for(
    cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh, *, hbm_budget: float = 12e9
) -> CellPlan:
    """Baseline plan: fewest microbatches whose estimated footprint fits HBM
    (budget < 16 GB leaves headroom for fragmentation + XLA temps; the
    dry-run verifies with ``memory_analysis`` and auto-bumps on overflow).

    Footprint model (per device):
      fixed  = params x (2 bf16 + 8 fp32 moments + 4 fp32 grads) / tp
      per-mb = tokens_mb x [ 3 dtype-bytes x Vp/tp   (logits + its grad)
                           + L x D x 2 / sp          (remat layer carries)
                           + ~12 x D x 2 / sp ]      (within-layer working set)
    """
    if shape.kind == "decode":
        return CellPlan(microbatches=1, seq_shard=False, remat=False)
    n_dev = mesh.devices.size
    tp = mesh.shape.get("model", 1)
    dp = 1
    for a in batch_axes(mesh, shape.global_batch):
        dp *= mesh.shape[a]
    seq_shard = shape.seq_len >= 2048 and shape.seq_len % tp == 0
    sp = tp if seq_shard else 1

    params = cfg.n_params()
    state_bytes = 2 + 8 + 4 if shape.kind == "train" else 2
    fixed = params * state_bytes / tp
    # ZeRO-3 when the parameter/optimizer footprint alone would crowd HBM
    fsdp = fixed > hbm_budget * 0.5
    if fsdp:
        fixed /= max(dp, 1)
    B, S, D, L = shape.global_batch, shape.seq_len, cfg.d_model, cfg.n_layers
    Vp = cfg.padded_vocab

    def per_mb_bytes(mb: int) -> float:
        tokens = (B // mb // dp) * S
        logits = tokens * (Vp / tp) * 4 * 3
        carries = tokens * L * D * 2 / sp
        working = tokens * 12 * D * 2 / sp
        return logits + carries + working

    mb = 1
    # cap: each microbatch must still divide the dp axes, or activations
    # silently replicate over 'data' and memory goes UP
    mb_cap = max(1, B // max(dp, 1))
    if shape.kind == "train":
        while (
            mb * 2 <= mb_cap
            and B % (mb * 2) == 0
            and fixed + per_mb_bytes(mb) > hbm_budget
        ):
            mb *= 2
    return CellPlan(
        microbatches=mb, seq_shard=seq_shard, remat=shape.kind == "train", fsdp=fsdp
    )


def skip_reason(arch: str, shape_name: str) -> str | None:
    canon = arch.replace("_", "-")
    if shape_name == "long_500k" and canon in FULL_ATTENTION_ARCHS:
        return "full-attention arch: 524k decode is unbounded-cache/quadratic (DESIGN.md §6)"
    return None


@dataclass
class Cell:
    arch: str
    shape_name: str
    cfg: ModelConfig
    shape: ShapeSpec
    plan: CellPlan
    kind: str                      # train | prefill | decode
    fn: Callable                   # the step function (unjitted)
    args: tuple                    # ShapeDtypeStruct pytrees
    in_shardings: tuple
    out_shardings: Any
    donate_argnums: tuple
    ctx: MeshCtx

    def lower(self):
        jitted = jax.jit(
            self.fn,
            in_shardings=self.in_shardings,
            out_shardings=self.out_shardings,
            donate_argnums=self.donate_argnums,
        )
        with use_mesh(self.ctx):
            return jitted.lower(*self.args)


def _sds(tree: Any) -> Any:
    return jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)


def _named(mesh: Mesh, spec_tree: Any) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda s: isinstance(s, P),
    )


def build_cell(
    arch: str,
    shape_name: str,
    mesh: Mesh,
    *,
    plan: CellPlan | None = None,
    smoke: bool = False,
) -> Cell:
    cfg = get_config(arch, smoke=smoke)
    shape = SHAPES[shape_name]
    plan = plan or plan_for(cfg, shape, mesh)
    if plan.extra:
        cfg = cfg.reduced(**plan.extra)   # e.g. attn_q_chunk for the perf pass
    kind = shape.kind
    bt = batch_axes(mesh, shape.global_batch)
    ctx = MeshCtx(mesh, bt, seq="model" if plan.seq_shard else None)
    key = jax.random.key(0)

    if kind == "train":
        tcfg = TrainStepConfig(microbatches=plan.microbatches, remat=plan.remat)
        state_shape = jax.eval_shape(lambda k: init_train_state(cfg, k), key)
        batch_shape = model_api.input_specs(cfg, shape, kind="train")
        state_sh = _named(mesh, state_pspecs(cfg, state_shape, mesh, fsdp=plan.fsdp))
        batch_sh = _named(mesh, batch_pspecs(batch_shape, mesh, shape.global_batch))
        fn = make_train_step(cfg, tcfg)
        return Cell(
            arch, shape_name, cfg, shape, plan, kind, fn,
            args=(_sds(state_shape), batch_shape),
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, None),
            donate_argnums=(0,) if plan.donate else (),
            ctx=ctx,
        )

    params_shape = jax.eval_shape(lambda k: transformer.init_params(cfg, k), key)
    params_sh = _named(mesh, param_pspecs(cfg, params_shape, mesh, fsdp=plan.fsdp))
    B = shape.global_batch
    cache_shape = jax.eval_shape(
        lambda: transformer.init_cache(cfg, B, shape.seq_len)
    )
    cache_sh = _named(mesh, cache_pspecs(cfg, cache_shape, mesh, B))

    if kind == "prefill":
        batch_shape = model_api.input_specs(cfg, shape, kind="prefill")
        batch_sh = _named(mesh, batch_pspecs(batch_shape, mesh, B))
        fn = make_prefill_step(cfg)
        return Cell(
            arch, shape_name, cfg, shape, plan, kind, fn,
            args=(_sds(params_shape), _sds(cache_shape), batch_shape),
            in_shardings=(params_sh, cache_sh, batch_sh),
            out_shardings=(None, cache_sh),
            donate_argnums=(1,) if plan.donate else (),
            ctx=ctx,
        )

    # decode: one new token against a seq_len-deep cache
    tokens_shape = model_api.input_specs(cfg, shape, kind="decode")
    tokens_sh = _named(mesh, batch_pspecs(tokens_shape, mesh, B))
    fn = make_decode_step(cfg)
    return Cell(
        arch, shape_name, cfg, shape, plan, kind, fn,
        args=(_sds(params_shape), _sds(cache_shape), tokens_shape["tokens"]),
        in_shardings=(params_sh, cache_sh, tokens_sh["tokens"]),
        out_shardings=(None, cache_sh),
        donate_argnums=(1,) if plan.donate else (),
        ctx=ctx,
    )


def cell_matrix(archs: list[str] | None = None) -> list[tuple[str, str]]:
    """The full (arch x shape) matrix, including skipped cells."""
    from repro.configs.base import list_archs

    archs = archs or list_archs()
    return [(a, s) for a in archs for s in SHAPES]
