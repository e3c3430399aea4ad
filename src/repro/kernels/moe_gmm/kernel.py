"""Grouped matmul Pallas kernel for a dropless expert layer.

The rows come sorted by group (expert): group ``g`` owns rows
``offsets[g] .. offsets[g+1]`` and multiplies them by its own matrix
``w[g]``; rows past ``offsets[G]`` belong to no group and come out zero.
There is no capacity: a group takes as many rows as were routed to it.
The matrices may be one layer's of a stack ``[L, G, K, N]``: the layer is
a scalar-prefetch operand too, and the kernel reads that layer's blocks in
place, so that a layer scan never slices (and copies) the stack.

The grid walks ``(N / bn, visits)``.  A visit is one (group, row tile)
pair that holds rows of that group, in order of group and then tile, so
that the visits of one output tile follow each other and the tile stays
in VMEM between them, each visit writing only its own group's rows.  The
visit list is built on the device from the group sizes and reaches the
kernel by scalar prefetch; its static length bounds the real count (at
most ``M / bm + G``), and the slots past the real count repeat the last
visit's block indices, so that they fetch nothing, and compute nothing.
An empty group has no visit: its matrix is never read.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["moe_gmm_kernel_call"]


def _visits(group_sizes: jax.Array, n_rows: int, bm: int):
    """The visit list of ``group_sizes`` over ``n_rows`` rows in tiles of
    ``bm``: (group of each visit, its row tile, the number of real visits,
    the groups' row offsets [G + 1])."""
    G = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])
    first = offsets[:-1] // bm
    n_tiles = jnp.where(sizes > 0, (offsets[1:] - 1) // bm - first + 1, 0)
    ends = jnp.cumsum(n_tiles)                   # visits up to each group
    n_visits = ends[-1]
    v = jnp.minimum(jnp.arange(n_rows // bm + G, dtype=jnp.int32),
                    jnp.maximum(n_visits - 1, 0))
    group = jnp.minimum(jnp.searchsorted(ends, v, side="right"), G - 1)
    tile = first[group] + v - (ends[group] - n_tiles[group])
    return (group.astype(jnp.int32), tile.astype(jnp.int32),
            jnp.reshape(n_visits, (1,)).astype(jnp.int32), offsets)


def _kernel(layer_ref, group_ref, tile_ref, nv_ref, off_ref, x_ref, w_ref,
            o_ref, *, bm: int):
    del layer_ref                        # consumed by the index map
    v = pl.program_id(1)

    @pl.when(v < nv_ref[0])
    def _visit():
        g = group_ref[v]
        rows = tile_ref[v] * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
        mine = (rows >= off_ref[g]) & (rows < off_ref[g + 1])
        acc = jnp.dot(x_ref[...], w_ref[0, 0], preferred_element_type=jnp.float32)
        o_ref[...] = jnp.where(mine, acc.astype(o_ref.dtype), o_ref[...])


def moe_gmm_kernel_call(
    x: jax.Array,            # [M, K] rows sorted by group
    w: jax.Array,            # [L, G, K, N]
    layer: jax.Array,        # [] int32: the layer of w read
    group_sizes: jax.Array,  # [G] int32, summing to at most M
    *,
    block_m: int,
    block_n: int,
    interpret: bool,
) -> jax.Array:
    M, K = x.shape
    _, G, _, N = w.shape
    bm = min(block_m, M)
    bn = min(block_n, N)
    if M % bm or N % bn:
        raise ValueError(f"blocks must tile the array: M={M} bm={bm} N={N} bn={bn}")
    group, tile, n_visits, offsets = _visits(group_sizes, M, bm)

    def x_map(n, v, lyr, grp, til, nv, off):
        return til[v], 0

    def w_map(n, v, lyr, grp, til, nv, off):
        return lyr[0], grp[v], 0, n

    def o_map(n, v, lyr, grp, til, nv, off):
        return til[v], n

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(N // bn, group.shape[0]),
        in_specs=[pl.BlockSpec((bm, K), x_map),
                  pl.BlockSpec((1, 1, K, bn), w_map)],
        out_specs=pl.BlockSpec((bm, bn), o_map),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bm=bm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), group, tile, n_visits,
      offsets, x, w)
    # tiles past the last group's rows are never visited
    return jnp.where(jnp.arange(M)[:, None] < offsets[-1], out, 0)
