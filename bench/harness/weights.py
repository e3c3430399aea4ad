"""Random weights from the seed, made on the device in one jitted call, in
the layout the program reads (layers stacked on a leading axis) and the type
it serves them in.

Matrices are normal with standard deviation ``fan_in ** -0.5``; norm gains
(stored as ``scale``, applied as ``1 + scale``) are normal with standard
deviation 0.1, so that a reference that dropped them would disagree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .flops import head_dim


def shapes(m: dict) -> dict:
    L, d, f, V = m["n_layers"], m["d_model"], m["d_ff"], m["vocab_size"]
    hd, H, Hkv = head_dim(m), m["n_heads"], m["n_kv_heads"]
    return {
        "embed": (V, d), "final_norm": (d,), "unembed": (d, V),
        "layers": {
            "ln1": (L, d), "ln2": (L, d),
            "attn": {"wq": (L, d, H * hd), "wk": (L, d, Hkv * hd),
                     "wv": (L, d, Hkv * hd), "wo": (L, H * hd, d)},
            "mlp": {"w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d)},
        },
    }


def _std(path: str, shape: tuple) -> float:
    if "norm" in path or path.endswith(("ln1", "ln2")):
        return 0.1
    if path == "embed":
        return shape[-1] ** -0.5
    return shape[-2] ** -0.5          # fan-in of a [.., in, out] matrix


def make(m: dict, key, dtype=None, out_shardings=None) -> dict:
    """The weights for configuration ``m`` from ``key``, made on the device
    in ``m["dtype"]`` (or ``dtype``); ``out_shardings`` places them already
    split over a mesh."""
    dtype = dtype or getattr(jnp, m["dtype"])
    tree = shapes(m)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=lambda s: isinstance(s, tuple))
    paths = ["/".join(str(getattr(k, "key", k)) for k in p)
             for p, _ in jax.tree_util.tree_flatten_with_path(
                 tree, is_leaf=lambda s: isinstance(s, tuple))[0]]

    def init(key):
        keys = jax.random.split(key, len(leaves))
        out = [jax.random.normal(k, s, dtype)
               * jnp.asarray(_std(p.rsplit("/", 1)[-1], s), dtype)
               for k, s, p in zip(keys, leaves, paths)]
        return jax.tree.unflatten(treedef, out)

    fn = jax.jit(init) if out_shardings is None else jax.jit(
        init, out_shardings=out_shardings)
    return fn(key)
