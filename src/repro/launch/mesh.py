"""Production meshes (TPU v5e pods).

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init;
everything else sees the real single CPU device.

Topology: a v5e pod is a 16x16 ICI torus (256 chips). ``data`` x ``model``
maps onto it so that the model axis is ICI-contiguous (TP collectives stay
on-pod); the ``pod`` axis crosses DCN and only carries gradient
all-reduces. The same constructor scales to any pod count — 1000+ chips is
``multi_pod`` with more pods (e.g. (8, 16, 16) = 2048 chips); nothing in the
sharding rules depends on the pod count.

Every mesh is built with ``Auto`` axes: the model code places activations
with ``with_sharding_constraint`` (``dist.sharding.shard``), which refuses
``Explicit`` axes, ``jax.make_mesh``'s default.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh", "describe_mesh"]


def make_production_mesh(*, multi_pod: bool = False, n_pods: int = 2):
    shape = (n_pods, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], *, devices=None):
    """An ``Auto``-axis mesh over ``devices`` (default: all of them)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def describe_mesh(mesh) -> str:
    return " x ".join(f"{a}={mesh.shape[a]}" for a in mesh.axis_names)
