"""Flash-decode Pallas kernel: one query token vs a long KV cache.

Decode is memory-bound (arithmetic intensity ~1 flop/byte: every cached
K/V byte is read once per step), so the tiling goal is pure streaming:
grid = (B, S/bk) with the KV axis innermost carrying the online-softmax
state; all Hq heads of a batch element are processed per tile (q is tiny).

Slot-position masking (``kv_pos`` per cache slot, -1 = empty) makes the
same kernel serve linear caches and the ring buffers of sliding-window
archs.  VMEM per step with bk=512, Hkv*hd<=8k: k/v tiles ~8 MB bf16 —
the tile streams at HBM bandwidth, which IS the roofline for this op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attention_kernel_call", "paged_decode_attention_kernel_call"]

_NEG_INF = -1e30


def _kernel(
    q_ref, k_ref, v_ref, pos_ref, qpos_ref, o_ref, acc_ref, m_ref, l_ref,
    *, n_kv: int, G: int, window: int | None, scale: float,
):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale     # [Hq, hd]
    k = k_ref[0]                                 # [bk, Hkv, hd]
    v = v_ref[0]
    kv_pos = pos_ref[...]                        # [bk]
    q_pos = qpos_ref[0]

    Hq, hd = q.shape
    bk, Hkv, _ = k.shape
    qg = q.reshape(Hkv, G, hd)
    # s[h, g, c] = sum_d qg[h,g,d] * k[c,h,d]
    s = jax.lax.dot_general(
        qg, k.astype(jnp.float32),
        (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32,
    )                                            # [Hkv, G, bk]
    keep = (kv_pos >= 0) & (kv_pos <= q_pos)
    if window is not None:
        keep &= kv_pos > q_pos - window
    s = jnp.where(keep[None, None, :], s, _NEG_INF)

    sm = s.reshape(Hq, bk)
    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_cur = jnp.maximum(m_prev, sm.max(axis=-1, keepdims=True))
    p = jnp.exp(sm - m_cur)                      # [Hq, bk]
    corr = jnp.exp(m_prev - m_cur)
    l_cur = l_prev * corr + p.sum(axis=-1, keepdims=True)
    # pv[h, g, d] = sum_c p[h,g,c] * v[c,h,d]
    pv = jax.lax.dot_general(
        p.reshape(Hkv, G, bk), v.astype(jnp.float32),
        (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32,
    )                                            # [Hkv, G, hd]
    acc_ref[...] = acc_ref[...] * corr + pv.reshape(Hq, hd)
    m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_cur, l_ref.shape)

    @pl.when(ik == n_kv - 1)
    def _done():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention_kernel_call(
    q: jax.Array,        # [B, Hq, hd]
    k_cache: jax.Array,  # [B, S, Hkv, hd]
    v_cache: jax.Array,
    kv_pos: jax.Array,   # [S] int32
    q_pos: jax.Array,    # [] int32
    *,
    window: int | None,
    block_k: int,
    interpret: bool,
) -> jax.Array:
    B, Hq, hd = q.shape
    _, S, Hkv, _ = k_cache.shape
    if Hq % Hkv != 0:
        raise ValueError(f"Hq={Hq} not a multiple of Hkv={Hkv}")
    G = Hq // Hkv
    bk = min(block_k, S)
    if S % bk != 0:
        raise ValueError(f"block_k must tile the cache: S={S} bk={bk}")
    n_kv = S // bk

    kern = functools.partial(
        _kernel, n_kv=n_kv, G=G, window=window, scale=hd ** -0.5,
    )
    return pl.pallas_call(
        kern,
        grid=(B, n_kv),
        in_specs=[
            pl.BlockSpec((1, Hq, hd), lambda b, ik: (b, 0, 0)),
            pl.BlockSpec((1, bk, Hkv, hd), lambda b, ik: (b, ik, 0, 0)),
            pl.BlockSpec((1, bk, Hkv, hd), lambda b, ik: (b, ik, 0, 0)),
            pl.BlockSpec((bk,), lambda b, ik: (ik,)),
            pl.BlockSpec((1,), lambda b, ik: (0,)),
        ],
        out_specs=pl.BlockSpec((1, Hq, hd), lambda b, ik: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((Hq, hd), jnp.float32),
            pltpu.VMEM((Hq, 128), jnp.float32),
            pltpu.VMEM((Hq, 128), jnp.float32),
        ],
        interpret=interpret,
    )(q, k_cache, v_cache, kv_pos, q_pos.reshape(1))


# ---------------------------------------------------------------------------
# paged variant: the KV lives in a global page pool [P, L, ps, Hkv, hd] (a
# physical page holds its tokens for every layer) and each batch row owns a
# page *table*.  The layer and the table are scalar-prefetch operands
# (PrefetchScalarGridSpec), so the BlockSpec index map itself chases them:
# grid step (b, ip) DMAs layer `layer` of page table[b, ip] straight out of
# the pool — the kernel never materializes a gathered [B, S] cache, nor a
# slice of one layer's pool.  Unmapped entries (table[b, ip] < 0) clamp to page 0 for the DMA and
# are masked out of the online softmax in the body.
#
# The pool holds the positions below each row's q_pos; the row's own token
# (its K/V computed this step, not yet in the pool) seeds the online softmax,
# so the decode step reads the pool and never writes it.
# ---------------------------------------------------------------------------

def _paged_kernel(
    layer_ref, table_ref, qpos_ref,      # scalar-prefetch: [1], [B, n_pt], [B]
    q_ref, knew_ref, vnew_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, n_pt: int, ps: int, G: int, window: int | None, scale: float,
):
    del layer_ref                        # consumed by the index maps
    b = pl.program_id(0)
    ip = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale     # [Hq, hd]

    @pl.when(ip == 0)
    def _init():
        # the fresh token at q_pos: k_new/v_new come repeated per query head
        s_new = jnp.sum(q * knew_ref[0].astype(jnp.float32), axis=-1,
                        keepdims=True)           # [Hq, 1]
        acc_ref[...] = vnew_ref[0].astype(jnp.float32)
        m_ref[...] = jnp.broadcast_to(s_new, m_ref.shape)
        l_ref[...] = jnp.ones_like(l_ref)

    k = k_ref[0, 0]                              # [ps, Hkv, hd]
    v = v_ref[0, 0]
    page = table_ref[b, ip]
    q_pos = qpos_ref[b]
    kv_pos = ip * ps + jax.lax.broadcasted_iota(jnp.int32, (ps,), 0)

    Hq, hd = q.shape
    _, Hkv, _ = k.shape
    qg = q.reshape(Hkv, G, hd)
    s = jax.lax.dot_general(
        qg, k.astype(jnp.float32),
        (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32,
    )                                            # [Hkv, G, ps]
    keep = (page >= 0) & (kv_pos < q_pos)
    if window is not None:
        keep &= kv_pos > q_pos - window
    s = jnp.where(keep[None, None, :], s, _NEG_INF)

    sm = s.reshape(Hq, ps)
    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_cur = jnp.maximum(m_prev, sm.max(axis=-1, keepdims=True))
    p = jnp.exp(sm - m_cur)
    corr = jnp.exp(m_prev - m_cur)
    l_cur = l_prev * corr + p.sum(axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.reshape(Hkv, G, ps), v.astype(jnp.float32),
        (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32,
    )
    acc_ref[...] = acc_ref[...] * corr + pv.reshape(Hq, hd)
    m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_cur, l_ref.shape)

    @pl.when(ip == n_pt - 1)
    def _done():
        o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def paged_decode_attention_kernel_call(
    q: jax.Array,           # [B, Hq, hd]
    k_new: jax.Array,       # [B, Hkv, hd] the row's own K/V at q_pos
    v_new: jax.Array,
    k_pages: jax.Array,     # [P, L, ps, Hkv, W], W >= hd (lane-padded)
    v_pages: jax.Array,
    layer: jax.Array,       # [] int32
    page_table: jax.Array,  # [B, n_pt] int32, -1 = unmapped
    q_pos: jax.Array,       # [B] int32
    *,
    window: int | None,
    interpret: bool,
) -> jax.Array:
    B, Hq, hd = q.shape
    _, _, ps, Hkv, W = k_pages.shape
    n_pt = page_table.shape[1]
    if Hq % Hkv != 0:
        raise ValueError(f"Hq={Hq} not a multiple of Hkv={Hkv}")
    G = Hq // Hkv
    # the row's operands take the pool's lane width (zeros past hd add
    # nothing to q.k or to the output, which drops them); the fresh K/V
    # come once per query head, so the seed is a plain product with q (a
    # few KB a row, against a page walk of megabytes)
    lanes = [(0, 0), (0, 0), (0, W - hd)]
    q = jnp.pad(q, lanes)
    k_new = jnp.pad(jnp.repeat(k_new, G, axis=1), lanes)
    v_new = jnp.pad(jnp.repeat(v_new, G, axis=1), lanes)

    kern = functools.partial(
        _paged_kernel, n_pt=n_pt, ps=ps, G=G, window=window, scale=hd ** -0.5,
    )
    row = pl.BlockSpec((1, Hq, W), lambda b, ip, lyr, tbl, qp: (b, 0, 0))
    page = pl.BlockSpec(
        (1, 1, ps, Hkv, W),
        lambda b, ip, lyr, tbl, qp: (jnp.maximum(tbl[b, ip], 0), lyr[0], 0, 0, 0),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_pt),
        in_specs=[row, row, row, page, page],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((Hq, W), jnp.float32),
            pltpu.VMEM((Hq, 128), jnp.float32),
            pltpu.VMEM((Hq, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, W), q.dtype),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), page_table, q_pos,
      q, k_new, v_new, k_pages, v_pages)
    return out[..., :hd]
