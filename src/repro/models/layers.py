"""Shared neural-net layers (pure JAX, functional).

Attention is *chunked* (online-softmax over KV chunks, flash-attention
semantics in pure jnp) so that 32k+ sequences never materialize an
[S, S] score matrix — this keeps the dry-run HLO's memory roofline honest
and matches the Pallas kernel's blocking (kernels/flash_attention).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = [
    "rms_norm",
    "apply_rope",
    "rope_inv_freq",
    "yarn_inv_freq",
    "yarn_scale",
    "sinusoidal_positions",
    "glu_ffn",
    "chunked_attention",
    "decode_attention",
    "masked_attention",
    "causal_conv1d",
    "linear_recurrence_chunked",
]

_NEG_INF = -1e30


def _in_window(kv_pos: jax.Array, q_pos: jax.Array, window) -> jax.Array:
    """Keep-mask of a sliding window: entries among the last ``window``
    positions up to the query's; a window of 0 keeps every entry.  The
    window is an int or an int32 scalar that may be traced (a layer's
    window read inside the layer scan)."""
    return (window <= 0) | (kv_pos > q_pos - window)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))).astype(dt)


def rope_inv_freq(hd: int, theta: float) -> jax.Array:
    """Rotary inverse frequencies ``theta ** (-2i / hd)``, ``i < hd/2``."""
    half = hd // 2
    return jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)


def yarn_inv_freq(hd: int, theta: float, factor: float, original: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0) -> jax.Array:
    """YaRN's inverse frequencies (HF ``_compute_yarn_parameters``, with
    ``truncate``): dimensions that turn fewer than ``beta_slow`` times over
    the ``original`` context are interpolated by ``factor``, those that turn
    more than ``beta_fast`` times are kept, and a linear ramp blends the
    ones between."""
    def correction_dim(rotations):
        return hd * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), hd - 1)
    if low == high:
        high += 0.001
    extrap = rope_inv_freq(hd, theta)
    ramp = jnp.clip((jnp.arange(hd // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    return extrap / factor * ramp + extrap * (1 - ramp)


def yarn_scale(factor: float) -> float:
    """YaRN's attention factor, ``0.1 ln(factor) + 1``, which multiplies cos
    and sin (so q.k by its square)."""
    return 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 0.0, *,
               inv_freq: jax.Array | None = None,
               scale: jax.Array | float | None = None) -> jax.Array:
    """Rotary embedding. x: [..., S, H, hd]; positions: [..., S].

    The frequencies are ``inv_freq`` ([hd/2], may be traced) or, when it is
    not given, those of ``theta`` (no rotation for ``theta <= 0``);
    ``scale`` multiplies cos and sin (YaRN's attention factor)."""
    if inv_freq is None:
        if theta <= 0:
            return x
        inv_freq = rope_inv_freq(x.shape[-1], theta)
    half = x.shape[-1] // 2
    ang = positions[..., :, None].astype(jnp.float32) * inv_freq  # [..., S, half]
    cos = jnp.cos(ang)[..., :, None, :]  # broadcast over heads
    sin = jnp.sin(ang)[..., :, None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def sinusoidal_positions(n: int, d: int, dtype=jnp.float32) -> jax.Array:
    """Whisper-style sinusoidal absolute position table [n, d]."""
    half = d // 2
    freqs = jnp.exp(-math.log(10_000.0) * jnp.arange(half, dtype=jnp.float32) / max(half - 1, 1))
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


def glu_ffn(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array, act: str) -> jax.Array:
    """SwiGLU (act='silu') / GeGLU (act='gelu') feed-forward.

    Megatron-SP constraints made explicit (no-ops without a mesh): the
    hidden is column-sharded with the sequence *gathered*, the output
    returns to sequence-sharded.  This pins BOTH directions of the VJP:
    dY gathers over seq before the dW einsum (local column dW — no
    full-matrix gradient all-reduce) and dX reduce-scatters.  Without
    these, GSPMD picked partial-dW + f32 full all-reduce per layer per
    microbatch — 2.6 TB/step/device on command-r (EXPERIMENTS.md §Perf C1).
    """
    from repro.dist.sharding import shard

    a = jnp.einsum("...d,df->...f", x, w_gate)
    a = shard(a, "batch", None, "model")
    a = jax.nn.silu(a) if act == "silu" else jax.nn.gelu(a)
    b = jnp.einsum("...d,df->...f", x, w_up)
    b = shard(b, "batch", None, "model")
    out = jnp.einsum("...f,fd->...d", a * b, w_down)
    return shard(out, "batch", "seq", None)


def _mask_chunk(
    q_pos: jax.Array,  # [Sq]
    kv_pos: jax.Array,  # [Ck]
    *,
    causal: bool,
    window: int | jax.Array,
    kv_len: jax.Array | None,
) -> jax.Array:
    """Boolean keep-mask [Sq, Ck]."""
    m = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), bool)
    if causal:
        m &= kv_pos[None, :] <= q_pos[:, None]
    m &= _in_window(kv_pos[None, :], q_pos[:, None], window)
    if kv_len is not None:
        m &= kv_pos[None, :] < kv_len
    return m


def chunked_attention(
    q: jax.Array,  # [B, Sq, Hq, hd]
    k: jax.Array,  # [B, Skv, Hkv, hd]
    v: jax.Array,  # [B, Skv, Hkv, hd]
    *,
    causal: bool = True,
    window: int | jax.Array = 0,
    q_offset: int = 0,
    kv_len: jax.Array | None = None,
    chunk: int = 2048,
    q_chunk: int = 2048,
) -> jax.Array:
    """GQA attention, online-softmax over KV chunks AND blocked over Q chunks
    (flash semantics in both directions: peak temp is one
    [B, q_chunk, H, chunk] score tile, never [Sq, Skv]).

    Returns [B, Sq, Hq, hd]. ``kv_len``: optional valid KV length (decode
    against a longer cache). ``q_offset``: absolute position of q[0].
    """
    B, Sq, Hq, hd = q.shape
    _, Skv, Hkv, _ = k.shape
    if Hq % Hkv != 0:
        raise ValueError(f"n_heads {Hq} not a multiple of n_kv_heads {Hkv}")
    G = Hq // Hkv
    scale = hd ** -0.5

    if chunk <= 0 or Skv % chunk != 0:
        chunk = Skv  # small sequences: single chunk
    n_kv = Skv // chunk
    if q_chunk <= 0 or Sq % q_chunk != 0:
        q_chunk = Sq  # q_chunk=0: no q loop (q rows sharded over the mesh)
    n_q = Sq // q_chunk

    qg = q.reshape(B, n_q, q_chunk, Hkv, G, hd).transpose(1, 0, 2, 3, 4, 5) * scale
    kc = k.reshape(B, n_kv, chunk, Hkv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_kv, chunk, Hkv, hd).transpose(1, 0, 2, 3, 4)

    def one_q_block(inp):
        qi, qb = inp  # qb: [B, q_chunk, Hkv, G, hd]
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        acc0 = jnp.zeros((B, q_chunk, Hkv, G, hd), jnp.float32)
        m0 = jnp.full((B, q_chunk, Hkv, G), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, q_chunk, Hkv, G), jnp.float32)

        def step(carry, inp):
            acc, m, l = carry
            ci, kb, vb = inp
            kv_pos = ci * chunk + jnp.arange(chunk)
            keep = _mask_chunk(q_pos, kv_pos, causal=causal, window=window, kv_len=kv_len)
            # scores: [B, Cq, Hkv, G, Ck]
            s = jnp.einsum("bqhgd,bchd->bqhgc", qb, kb).astype(jnp.float32)
            s = jnp.where(keep[None, :, None, None, :], s, _NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bqhgc,bchd->bqhgd", p.astype(kb.dtype), vb).astype(jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (acc_new, m_new, l_new), None

        (acc, m, l), _ = jax.lax.scan(
            step, (acc0, m0, l0), (jnp.arange(n_kv), kc, vc)
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.astype(q.dtype)  # [B, Cq, Hkv, G, hd]

    if n_q == 1:
        out = one_q_block((jnp.asarray(0), qg[0]))[:, None]
        out = out.reshape(B, 1, q_chunk, Hq, hd)
    else:
        out = jax.lax.map(one_q_block, (jnp.arange(n_q), qg))  # [n_q, B, Cq, Hkv, G, hd]
        out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, n_q, q_chunk, Hq, hd)
    return out.reshape(B, Sq, Hq, hd)


def decode_attention(
    q: jax.Array,        # [B, 1, Hq, hd]
    k_cache: jax.Array,  # [B, Smax, Hkv, hd] (linear or ring buffer)
    v_cache: jax.Array,
    kv_pos: jax.Array,   # [Smax] | [B, Smax] absolute position per slot; -1 = empty
    q_pos: jax.Array,    # [] | [B] absolute position of the query token
    *,
    window: int | jax.Array = 0,
) -> jax.Array:
    """Single-token attention against a KV cache (memory-bound path).

    Slot-position masking handles both linear caches (kv_pos = 0..len-1,
    rest -1) and ring buffers for sliding-window archs (slot s holds absolute
    position kv_pos[s]).  A 2-D ``kv_pos`` (with ``q_pos`` per batch row)
    is the continuous-batching layout: every row is an independent request
    at its own decode position over its own slice of the shared cache.
    """
    B, _, Hq, hd = q.shape
    _, Smax, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, hd) * hd ** -0.5
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache).astype(jnp.float32)
    if kv_pos.ndim == 2:
        qp = q_pos[:, None]
        keep = (kv_pos >= 0) & (kv_pos <= qp) & _in_window(kv_pos, qp, window)
        keep = keep[:, None, None, :]
    else:
        keep = ((kv_pos >= 0) & (kv_pos <= q_pos)
                & _in_window(kv_pos, q_pos, window))
        keep = keep[None, None, None, :]
    s = jnp.where(keep, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache)
    return out.reshape(B, 1, Hq, hd).astype(q.dtype)


def masked_attention(
    q: jax.Array,        # [B, Sq, Hq, hd]
    k: jax.Array,        # [B, Skv, Hkv, hd]
    v: jax.Array,
    kv_pos: jax.Array,   # [Skv] | [B, Skv] absolute position per entry; -1 = empty
    q_pos: jax.Array,    # [Sq]  | [B, Sq] absolute position per query row
    *,
    window: int | jax.Array = 0,
) -> jax.Array:
    """Position-table-masked GQA attention for ``Sq >= 1`` query rows.

    The multi-token generalization of :func:`decode_attention` (identical
    masking semantics and softmax math): every (query, entry) pair is kept
    iff the entry is occupied, causally visible, and inside the sliding
    window.  Used by the paged chunked-prefill path, where a prompt chunk
    attends to gathered context pages (arbitrary position tables) plus its
    own freshly-computed K/V.
    """
    B, Sq, Hq, hd = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, hd) * hd ** -0.5
    s = jnp.einsum("bqhgd,bshd->bhgqs", qg, k).astype(jnp.float32)
    kvp = kv_pos if kv_pos.ndim == 2 else kv_pos[None]    # [B|1, Skv]
    qp = q_pos if q_pos.ndim == 2 else q_pos[None]        # [B|1, Sq]
    keep = ((kvp[:, None, :] >= 0) & (kvp[:, None, :] <= qp[:, :, None])
            & _in_window(kvp[:, None, :], qp[:, :, None], window))
    s = jnp.where(keep[:, None, None, :, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqs,bshd->bqhgd", p.astype(v.dtype), v)
    return out.reshape(B, Sq, Hq, hd).astype(q.dtype)


def causal_conv1d(x: jax.Array, w: jax.Array, cache: jax.Array | None = None):
    """Depthwise causal conv along the sequence axis.

    x: [B, S, C]; w: [K, C]. Returns ([B, S, C], new_cache [B, K-1, C]).
    ``cache`` carries the last K-1 positions for streaming decode.
    """
    K = w.shape[0]
    if cache is None:
        cache = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([cache, x], axis=1)
    out = sum(xp[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(K))
    new_cache = xp[:, -(K - 1):, :] if K > 1 else cache
    return out.astype(x.dtype), new_cache


def linear_recurrence_chunked(
    a: jax.Array,  # [B, S, ...] decay
    b: jax.Array,  # [B, S, ...] input
    h0: jax.Array,  # [B, ...] initial state
    *,
    chunk: int = 128,
):
    """h_t = a_t * h_{t-1} + b_t along axis 1, returning (all h [B,S,...], h_S).

    Chunked: lax.scan over S/chunk chunks; inside a chunk, an associative
    scan. This bounds temporaries to O(chunk) (kernel-like blocking; the
    Pallas ssm_scan kernel implements the same schedule in VMEM).
    """
    B, S = a.shape[0], a.shape[1]
    if S % chunk != 0:
        chunk = S
    n = S // chunk

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2

    ac = jnp.moveaxis(a.reshape((B, n, chunk) + a.shape[2:]), 1, 0)
    bc = jnp.moveaxis(b.reshape((B, n, chunk) + b.shape[2:]), 1, 0)

    def step(h, inp):
        a_blk, b_blk = inp  # [B, chunk, ...]
        a_cum, b_scan = jax.lax.associative_scan(combine, (a_blk, b_blk), axis=1)
        h_blk = a_cum * h[:, None] + b_scan
        return h_blk[:, -1], h_blk

    h_last, hs = jax.lax.scan(step, h0, (ac, bc))
    hs = jnp.moveaxis(hs, 0, 1).reshape((B, S) + a.shape[2:])
    return hs, h_last
