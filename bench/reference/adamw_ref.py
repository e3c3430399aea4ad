"""Plain reference of a training step: a decoder's mean next-token
cross-entropy (the forward pass of ``arch``, the architecture module that
the configuration names, such as ``dense_gqa``), its gradient, global-norm
clipping and AdamW with decoupled weight decay (Loshchilov & Hutter),
bias-corrected moments, and a learning rate that warms up linearly and then
follows a cosine down to a tenth.  Every operation is float32; parameters
are kept in the type the configuration states.  Decay applies to weight
matrices, not to norm gains.  Nothing of the program under test is
imported.

``hooks``, where given, is a pair of functions applied to each layer's
weights and to the activations between layers: placement hints for a
mesh (``with_sharding_constraint``), which change no value.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

GAINS = ("ln1", "ln2", "final_norm")


def _name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def loss(arch, m: dict, params, tokens, labels, mode: str = "f32",
         hooks=None):
    """Mean next-token cross-entropy over every label; the layers swept by
    ``lax.scan``, each recomputed on the backward pass."""
    on_layer, on_x = hooks or ((lambda lp: lp), (lambda x: x))
    pos = jnp.arange(tokens.shape[1])
    x = on_x(arch.embed(params, tokens))

    @jax.checkpoint
    def body(x, layer):
        lp, i = layer
        return on_x(arch.block(m, on_layer(lp), i, x, pos, mode)), None

    x, _ = jax.lax.scan(body, x, (params["layers"], jnp.arange(m["n_layers"])))
    logits = arch.head(m, params, x, mode)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def grads(arch, m: dict, params, tokens, labels, mode: str = "f32",
          hooks=None):
    """(loss, gradient in float32)."""
    value, g = jax.value_and_grad(
        lambda p: loss(arch, m, p, tokens, labels, mode, hooks))(params)
    return value, jax.tree.map(lambda x: x.astype(jnp.float32), g)


def clip(opt: dict, g):
    """The gradient scaled down to a global norm of at most ``clip_norm``;
    and its norm before."""
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda x: x * scale, g), norm


def learning_rate(opt: dict, step: int):
    """Learning rate of step ``step`` (1-based)."""
    w, total, lr = opt["warmup_steps"], opt["total_steps"], opt["lr"]
    if step < w:
        return lr * step / max(w, 1)
    t = min(max((step - w) / max(total - w, 1), 0.0), 1.0)
    return lr * (0.1 + 0.9 * 0.5 * (1.0 + math.cos(math.pi * t)))


def apply(opt: dict, params, mom, vel, step: int):
    """Parameters after step ``step`` given its moments ``mom``, ``vel``."""
    c1, c2 = 1 - opt["b1"] ** step, 1 - opt["b2"] ** step
    lr = learning_rate(opt, step)

    def upd(path, p, a, v):
        p32 = p.astype(jnp.float32)
        d = (a / c1) / (jnp.sqrt(v / c2) + opt["eps"])
        if _name(path) not in GAINS:
            d = d + opt["weight_decay"] * p32
        return (p32 - lr * d).astype(p.dtype)

    return jax.tree_util.tree_map_with_path(upd, params, mom, vel)


def leaf_norms(tree) -> list:
    """Euclidean norm of each leaf, float32, in tree order."""
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]
