"""The jitted train step: loss -> grad -> AdamW, with microbatch gradient
accumulation (``lax.scan``) and per-layer remat.

State layout (a flat dict so dist/sharding.state_pspecs can rule-match):

    {"params": ..., "m": ..., "v": ..., "step": i32[]}

Microbatching reshapes every batch leaf [B, ...] -> [n_micro, B/n_micro, ...]
and accumulates fp32 grads across a scan — the standard pod-scale recipe for
fitting large global batches; it also bounds activation memory to one
microbatch.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeSpec
from repro.models import api as model_api
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.optim.schedule import linear_warmup_cosine

__all__ = [
    "TrainStepConfig",
    "init_train_state",
    "make_train_step",
    "lm_loss_fn",
    "compile_lm_loss",
]


@dataclass(frozen=True)
class TrainStepConfig:
    microbatches: int = 1
    remat: bool = True
    adamw: AdamWConfig = field(default_factory=AdamWConfig)
    warmup_steps: int = 100
    total_steps: int = 10_000
    grad_dtype: Any = jnp.float32    # accumulation dtype


def init_train_state(cfg: ModelConfig, key, adamw_cfg: AdamWConfig | None = None) -> dict:
    from repro.models import transformer

    params = transformer.init_params(cfg, key)
    opt = adamw_init(params, adamw_cfg)
    return {"params": params, **opt}


def lm_loss_fn(model_cfg: ModelConfig, *, remat: bool = False) -> Callable:
    """The scalar LM loss as a plain ``(params, batch) -> loss`` callable —
    the capture target for ``repro.api.compile``."""

    def loss(params, batch):
        return model_api.lm_loss(model_cfg, params, batch, remat=remat)[0]

    loss.__name__ = f"{model_cfg.name}.lm_loss"
    return loss


def compile_lm_loss(
    model_cfg: ModelConfig,
    shape: ShapeSpec,
    *,
    hw=None,
    backend: str = "host",
    remat: bool = False,
    grad: bool = False,
    unroll_layers: bool = True,
    runtime=None,
    **kw: Any,
):
    """``repro.api.compile`` the loss graph of a model at an input shape.

    Captures on abstract specs (no allocation); ``unroll_layers`` disables
    ``lax.scan`` over layers so the scheduler sees the per-layer operator
    DAG (leave it off to call the executable with real scanned params).
    ``grad=True`` captures ``value_and_grad`` instead — the paper's "one
    complete execution = one training iteration" graph.  ``runtime`` binds
    the executable to a shared :class:`repro.Runtime` (the process default
    otherwise), so a train step run next to a serve engine leases executors
    from — and shares calibration with — the same session.
    """
    from repro import api as graphi
    from repro.core import KNL7250
    from repro.models import transformer

    cfg = model_cfg.reduced(scan_layers=False) if unroll_layers else model_cfg
    fn = lm_loss_fn(cfg, remat=remat)
    if grad:
        fn = jax.value_and_grad(fn)
    params_spec = jax.eval_shape(lambda k: transformer.init_params(cfg, k), jax.random.key(0))
    batch_spec = model_api.input_specs(cfg, shape, kind="train")
    return graphi.compile(
        fn, params_spec, batch_spec,
        hw=hw or KNL7250, backend=backend, runtime=runtime,
        name=f"{cfg.name}.lm_loss" + ("+grad" if grad else ""),
        **kw,
    )


def make_train_step(
    model_cfg: ModelConfig, tcfg: TrainStepConfig | None = None
) -> Callable[[dict, dict], tuple[dict, dict]]:
    tcfg = tcfg or TrainStepConfig()

    def loss_fn(params, mb):
        loss, parts = model_api.lm_loss(model_cfg, params, mb, remat=tcfg.remat)
        return loss, parts

    def grads_of(params, batch):
        n = tcfg.microbatches
        if n == 1:
            (loss, parts), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
            grads = jax.tree.map(lambda g: g.astype(tcfg.grad_dtype), grads)
            return grads, loss, parts

        def split(x):
            b = x.shape[0]
            if b % n != 0:
                raise ValueError(
                    f"batch {b} not divisible by microbatches {n}")
            return x.reshape((n, b // n) + x.shape[1:])

        micro = jax.tree.map(split, batch)

        def acc_step(carry, mb):
            g_acc, loss_acc, ce_acc, aux_acc = carry
            (loss, parts), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
            g_acc = jax.tree.map(
                lambda a, b: a + b.astype(tcfg.grad_dtype), g_acc, g
            )
            return (g_acc, loss_acc + loss, ce_acc + parts["ce"], aux_acc + parts["aux"]), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, tcfg.grad_dtype), params)
        z = jnp.zeros((), jnp.float32)
        (g, loss, ce, aux), _ = jax.lax.scan(acc_step, (g0, z, z, z), micro)
        inv = 1.0 / n
        grads = jax.tree.map(lambda x: x * inv, g)
        return grads, loss * inv, {"ce": ce * inv, "aux": aux * inv}

    def train_step(state: dict, batch: dict) -> tuple[dict, dict]:
        params = state["params"]
        grads, loss, parts = grads_of(params, batch)
        lr = linear_warmup_cosine(
            state["step"] + 1, tcfg.adamw.lr, tcfg.warmup_steps, tcfg.total_steps
        )
        opt_state = {"m": state["m"], "v": state["v"], "step": state["step"]}
        new_params, new_opt, om = adamw_update(grads, params, opt_state, tcfg.adamw, lr=lr)
        new_state = {"params": new_params, **new_opt}
        metrics = {"loss": loss, "ce": parts["ce"], "aux": parts["aux"], **om}
        return new_state, metrics

    return train_step
