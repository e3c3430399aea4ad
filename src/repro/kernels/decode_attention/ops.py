"""jit-able wrapper for the flash-decode kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from .kernel import decode_attention_kernel_call

__all__ = ["decode_attention", "paged_decode_attention"]

_NEG_INF = -1e30


@partial(jax.jit, static_argnames=("window", "block_k", "interpret"))
def decode_attention(
    q: jax.Array,        # [B, 1, Hq, hd] (model layout) or [B, Hq, hd]
    k_cache: jax.Array,  # [B, S, Hkv, hd]
    v_cache: jax.Array,
    kv_pos: jax.Array,   # [S]
    q_pos: jax.Array,    # []
    *,
    window: int | None = None,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    squeeze = q.ndim == 4
    if squeeze:
        q = q[:, 0]
    out = decode_attention_kernel_call(
        q, k_cache, v_cache,
        kv_pos.astype(jnp.int32), q_pos.astype(jnp.int32),
        window=window, block_k=block_k, interpret=interpret,
    )
    return out[:, None] if squeeze else out


def paged_decode_attention(
    q: jax.Array,           # [B, 1, Hq, hd] (model layout) or [B, Hq, hd]
    k_new: jax.Array,       # [B, Hkv, hd] each row's own K/V at q_pos
    v_new: jax.Array,
    k_pages: jax.Array,     # [P, L, ps, Hkv, W] every layer's page pool,
                            # W >= hd (lane-padded; entries past hd unused)
    v_pages: jax.Array,
    layer: jax.Array,       # [] the layer whose pages are read
    page_table: jax.Array,  # [B, n_pt] physical page ids, -1 = unmapped
    q_pos: jax.Array,       # [B] absolute position per row
    *,
    window: int | jax.Array = 0,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Gather-by-page-table decode attention (the paged-KV hot path).

    Logical position of page-table entry ``(j, t)`` is ``j*ps + t``, so a
    request's pages reconstruct its linear KV cache without the cache ever
    existing contiguously.  The pool is read in place, by (page, layer), and
    holds the positions strictly below ``q_pos``; the row's own token
    (``k_new``/``v_new``, not yet written) is attended to as position
    ``q_pos``, so the caller writes it into the pool after the step.
    ``window`` (0: the whole context) is an int32 operand, not a
    compile-time constant: a layer scan passes each layer's own.  Two
    paths:

    - the pure-jnp gather path (default off-TPU): an explicit
      ``pages[table, layer]`` gather with the fresh token put in at
      ``q_pos``, then the same position-table-masked softmax as
      :func:`decode_attention`, so graphi fuses the gather into the
      attention group and ``StaticHostPlan`` replay sees a fixed-shape
      movement op;
    - the Pallas kernel (``REPRO_USE_PALLAS=1`` or real TPU), whose
      scalar-prefetch BlockSpec index map chases the layer and the page
      table directly, and whose online softmax starts from the fresh token.

    The path is resolved here, outside the jit, so it is part of the jit's
    cache key: flipping ``REPRO_USE_PALLAS`` never reuses the other path's
    trace.
    """
    from repro.kernels import kernels_enabled

    if use_kernel is None:
        use_kernel = kernels_enabled()
    if interpret is None:
        interpret = default_interpret()
    window = jnp.asarray(window, jnp.int32)
    return _paged_decode_attention(q, k_new, v_new, k_pages, v_pages, layer,
                                   window, page_table, q_pos,
                                   use_kernel=use_kernel, interpret=interpret)


@partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def _paged_decode_attention(q, k_new, v_new, k_pages, v_pages, layer, window,
                            page_table, q_pos, *, use_kernel: bool,
                            interpret: bool) -> jax.Array:
    from .kernel import paged_decode_attention_kernel_call

    squeeze = q.ndim == 4
    if squeeze:
        q = q[:, 0]
    page_table = page_table.astype(jnp.int32)
    q_pos = q_pos.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    if use_kernel:
        out = paged_decode_attention_kernel_call(
            q, k_new, v_new, k_pages, v_pages, layer, window, page_table,
            q_pos, interpret=interpret,
        )
        return out[:, None] if squeeze else out

    B, Hq, hd = q.shape
    ps, Hkv = k_pages.shape[2:4]
    n_pt = page_table.shape[1]
    clamped = jnp.maximum(page_table, 0)
    idx = jnp.arange(n_pt * ps)
    fresh = (idx[None] == q_pos[:, None])[..., None, None]
    kc = jnp.where(fresh, k_new[:, None],
                   k_pages[clamped, layer, ..., :hd].reshape(B, n_pt * ps, Hkv, hd))
    vc = jnp.where(fresh, v_new[:, None],
                   v_pages[clamped, layer, ..., :hd].reshape(B, n_pt * ps, Hkv, hd))
    mapped = jnp.repeat(page_table >= 0, ps, axis=1) | fresh[..., 0, 0]
    kv_pos = jnp.where(mapped, idx[None], -1)
    # masked softmax identical (op for op) to layers.decode_attention's 2-D
    # path: the paged engine must stay bit-exact with the per-slot engine
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, hd) * hd ** -0.5
    s = jnp.einsum("bhgd,bshd->bhgs", qg, kc).astype(jnp.float32)
    qp = q_pos[:, None]
    keep = ((kv_pos >= 0) & (kv_pos <= qp)
            & ((window <= 0) | (kv_pos > qp - window)))
    s = jnp.where(keep[:, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p.astype(vc.dtype), vc)
    out = out.reshape(B, Hq, hd).astype(q.dtype)
    return out[:, None] if squeeze else out
