"""Random weights from the seed, made on the device in one jitted call, in
the layout the program reads and the type it serves them in.

The tree is the architecture's (``shapes`` of the module the configuration
names, ``spec.architecture``): each leaf a shape, made in ``m["dtype"]``,
or a ``jax.ShapeDtypeStruct`` where the leaf keeps a type of its own (a
router the program holds in float32, say).  Leaves are drawn in the tree's
flattened order, one key each.

Matrices are normal with standard deviation ``fan_in ** -0.5``; norm gains
(leaves whose name holds ``norm`` or is ``ln1``/``ln2``, stored as
``scale`` and applied as ``1 + scale``) are normal with standard deviation
0.1, so that a reference that dropped them would disagree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .spec import architecture


def specs(m: dict) -> dict:
    """The architecture's weight tree as ``jax.ShapeDtypeStruct`` leaves;
    a leaf that names no type of its own takes ``m["dtype"]``."""
    dtype = getattr(jnp, m["dtype"])
    return jax.tree.map(
        lambda s: s if isinstance(s, jax.ShapeDtypeStruct)
        else jax.ShapeDtypeStruct(s, dtype),
        architecture(m).shapes(m), is_leaf=lambda s: isinstance(s, tuple))


def _std(path: str, shape: tuple) -> float:
    if "norm" in path or path.endswith(("ln1", "ln2")):
        return 0.1
    if path == "embed":
        return shape[-1] ** -0.5
    return shape[-2] ** -0.5          # fan-in of a [.., in, out] matrix


def make(m: dict, key, out_shardings=None) -> dict:
    """The weights for configuration ``m`` from ``key``, made on the device;
    ``out_shardings`` places them already split over a mesh."""
    with_paths, treedef = jax.tree_util.tree_flatten_with_path(specs(m))
    paths = ["/".join(str(getattr(k, "key", k)) for k in p)
             for p, _ in with_paths]
    leaves = [s for _, s in with_paths]

    def init(key):
        keys = jax.random.split(key, len(leaves))
        out = [jax.random.normal(k, s.shape, s.dtype)
               * jnp.asarray(_std(p.rsplit("/", 1)[-1], s.shape), s.dtype)
               for k, s, p in zip(keys, leaves, paths)]
        return jax.tree.unflatten(treedef, out)

    fn = jax.jit(init) if out_shardings is None else jax.jit(
        init, out_shardings=out_shardings)
    return fn(key)
