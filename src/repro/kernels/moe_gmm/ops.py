"""Grouped matmul of rows sorted by group: the kernel on a TPU (or with
``REPRO_USE_PALLAS=1``), ``jax.lax.ragged_dot`` elsewhere."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from .kernel import moe_gmm_kernel_call

__all__ = ["moe_gmm"]


def moe_gmm(
    x: jax.Array,            # [M, K] rows sorted by group
    w: jax.Array,            # [G, K, N], or [L, G, K, N] with ``layer``
    group_sizes: jax.Array,  # [G] rows of each group, in order
    *,
    layer: jax.Array | int | None = None,
    block_m: int = 128,
    block_n: int = 2304,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """``out[r] = x[r] @ w[g]`` (``w[layer, g]`` for a stack) for the rows
    ``r`` of group ``g``; rows past the groups' total come out zero.  [M, N]
    in ``x``'s type.  The kernel reads a stack's layer in place.  The path
    is resolved here, outside the jit, as for the paged decode attention."""
    from repro.kernels import kernels_enabled

    if use_kernel is None:
        use_kernel = kernels_enabled()
    if interpret is None:
        interpret = default_interpret()
    if layer is None:
        w, layer = w[None], 0
    return _moe_gmm(x, w, jnp.asarray(layer, jnp.int32), group_sizes,
                    block_m=block_m,
                    block_n=_block_n(w.shape[-1], block_n),
                    use_kernel=use_kernel, interpret=interpret)


def _block_n(n: int, most: int) -> int:
    """The widest multiple of 128 lanes that divides ``n`` and is at most
    ``most``; ``n`` itself when none is."""
    fits = [b for b in range(128, min(n, most) + 1, 128) if n % b == 0]
    return fits[-1] if fits else n


@partial(jax.jit, static_argnames=("block_m", "block_n", "use_kernel",
                                   "interpret"))
def _moe_gmm(x, w, layer, group_sizes, *, block_m: int, block_n: int,
             use_kernel: bool, interpret: bool) -> jax.Array:
    group_sizes = group_sizes.astype(jnp.int32)
    if not use_kernel:
        return jax.lax.ragged_dot(x, w[layer], group_sizes,
                                  preferred_element_type=jnp.float32
                                  ).astype(x.dtype)
    M = x.shape[0]
    bm = min(block_m, -(-M // 8) * 8)
    pad = -M % bm
    out = moe_gmm_kernel_call(jnp.pad(x, ((0, pad), (0, 0))), w, layer,
                              group_sizes,
                              block_m=bm, block_n=block_n,
                              interpret=interpret)
    return out[:M]
