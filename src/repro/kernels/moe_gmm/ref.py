"""Pure-jnp oracle for the grouped matmul of rows sorted by group."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["moe_gmm_ref"]


def moe_gmm_ref(x: jax.Array, w: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """x: [M, K] sorted by group; w: [G, K, N]; group_sizes: [G].  Row ``r``
    of group ``g`` (``offsets[g] <= r < offsets[g+1]``) is ``x[r] @ w[g]``
    with f32 accumulation; rows past every group are zero."""
    ends = jnp.cumsum(group_sizes)
    g = jnp.searchsorted(ends, jnp.arange(x.shape[0]), side="right")
    inside = g < w.shape[0]
    wr = w[jnp.minimum(g, w.shape[0] - 1)].astype(jnp.float32)  # [M, K, N]
    out = jnp.einsum("mk,mkn->mn", x.astype(jnp.float32), wr,
                     preferred_element_type=jnp.float32)
    return jnp.where(inside[:, None], out, 0).astype(x.dtype)
