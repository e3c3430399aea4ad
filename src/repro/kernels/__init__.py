"""Pallas TPU kernels for the compute hot-spots.

Each kernel ships as a subpackage: ``kernel.py`` (pl.pallas_call + explicit
BlockSpec VMEM tiling), ``ops.py`` (the jit-able wrapper with shape policy),
``ref.py`` (the pure-jnp oracle every test asserts against).

Off a TPU the kernels execute through ``interpret=True`` (the kernel body
lowers to plain XLA).  ``default_interpret()`` resolves the mode from the
backend; the models call the pure-jnp paths by default (same math as
ref.py) and switch to the kernels when ``REPRO_USE_PALLAS=1`` or a TPU
backend is present — interpret-mode kernels inside a 40-cell dry-run would
only slow compilation without changing the lowered collectives.
"""
from __future__ import annotations

import os

import jax

__all__ = ["default_interpret", "kernels_enabled"]


def default_interpret() -> bool:
    """interpret=True everywhere except a real TPU backend."""
    return jax.default_backend() != "tpu"


def kernels_enabled() -> bool:
    """Should the model layers route through the Pallas kernels?"""
    if os.environ.get("REPRO_USE_PALLAS", "") == "1":
        return True
    return jax.default_backend() == "tpu"
