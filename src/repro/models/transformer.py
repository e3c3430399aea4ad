"""Unified model: decoder LMs (dense/GQA/SWA/MoE), SSM (mamba), hybrid
(Griffin RG-LRU), encoder-decoder (whisper) and VLM (llava) backbones.

Functional API (pure fns over a params pytree):

    init_params(cfg, key)                       -> params
    forward(cfg, params, batch, remat=False)    -> (logits [B,S,Vp], aux)
    init_cache(cfg, batch, max_len)             -> cache
    prefill(cfg, params, batch, cache)          -> (logits [B,Vp], cache)
    decode_step(cfg, params, tokens [B,1], cache) -> (logits [B,Vp], cache)

Layers are stacked + ``lax.scan``-swept when the block pattern is homogeneous
(``cfg.scan_layers``), which keeps compile time flat in depth — essential for
the 40-cell dry-run sweep.  Heterogeneous archs (recurrentgemma) use a python
loop over per-layer param dicts.  What else differs between layers — the
attention window and the rope tables (``_layer_ops``) — is an operand of
the scan beside the weights, so windowed and full layers, plain and YaRN
rope, share one compiled layer.

Expert layers: training runs ``moe.moe_ffn`` (capacity routing, every
expert held); every serving path runs ``moe.expert_layer`` (dropless, the
experts held of those routed) and returns its counts.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

import numpy as np

from repro.configs.base import ModelConfig
from repro.dist.sharding import mesh_context, shard
from .griffin import init_rglru_cache, init_rglru_params, rglru_block, rglru_decode_step
from .layers import (
    apply_rope,
    chunked_attention,
    decode_attention,
    glu_ffn,
    masked_attention,
    rms_norm,
    rope_inv_freq,
    sinusoidal_positions,
    yarn_inv_freq,
    yarn_scale,
)
from .mamba import init_mamba_cache, init_mamba_params, mamba_block, mamba_decode_step
from .moe import expert_layer, init_moe_params, moe_ffn

__all__ = [
    "init_params",
    "forward",
    "init_cache",
    "prefill",
    "decode_step",
    "cache_insert_slot",
    "cache_evict_slot",
    "paged_supported",
    "init_paged_cache",
    "alloc_page",
    "free_pages",
    "paged_decode_step",
    "paged_prefill_chunk",
    "paged_insert_chunk",
    "paged_install_decode",
    "paged_copy_page",
]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_attn(key, cfg: ModelConfig, dtype):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    s = d ** -0.5
    so = (cfg.n_heads * hd) ** -0.5
    return {
        "wq": (jax.random.normal(kq, (d, cfg.n_heads * hd)) * s).astype(dtype),
        "wk": (jax.random.normal(kk, (d, cfg.n_kv_heads * hd)) * s).astype(dtype),
        "wv": (jax.random.normal(kv, (d, cfg.n_kv_heads * hd)) * s).astype(dtype),
        "wo": (jax.random.normal(ko, (cfg.n_heads * hd, d)) * so).astype(dtype),
    }


def _init_mlp(key, cfg: ModelConfig, dtype):
    if cfg.n_experts:
        return init_moe_params(key, cfg.d_model, cfg.d_ff, cfg.n_experts, dtype,
                               cfg.routed_experts)
    d, f = cfg.d_model, cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": (jax.random.normal(k1, (d, f)) * d ** -0.5).astype(dtype),
        "w_up": (jax.random.normal(k2, (d, f)) * d ** -0.5).astype(dtype),
        "w_down": (jax.random.normal(k3, (f, d)) * f ** -0.5).astype(dtype),
    }


def _init_layer(key, cfg: ModelConfig, kind: str, dtype, cross: bool = False):
    ks = jax.random.split(key, 5)
    d = cfg.d_model
    if kind == "ssm":
        return {"ln1": jnp.zeros((d,), dtype), "ssm": init_mamba_params(ks[0], cfg, dtype)}
    lp: dict[str, Any] = {"ln1": jnp.zeros((d,), dtype)}
    if kind == "attn":
        lp["attn"] = _init_attn(ks[0], cfg, dtype)
    elif kind == "rglru":
        lp["rnn"] = init_rglru_params(ks[0], cfg, dtype)
    else:
        raise ValueError(kind)
    if cross:
        lp["ln_x"] = jnp.zeros((d,), dtype)
        lp["xattn"] = _init_attn(ks[1], cfg, dtype)
    lp["ln2"] = jnp.zeros((d,), dtype)
    lp["mlp"] = _init_mlp(ks[2], cfg, dtype)
    return lp


def init_params(cfg: ModelConfig, key) -> dict:
    dtype = cfg.dtype
    keys = jax.random.split(key, cfg.n_layers + cfg.n_encoder_layers + 3)
    params: dict[str, Any] = {
        "embed": (
            jax.random.normal(keys[0], (cfg.padded_vocab, cfg.d_model)) * cfg.d_model ** -0.5
        ).astype(dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = (
            jax.random.normal(keys[1], (cfg.d_model, cfg.padded_vocab)) * cfg.d_model ** -0.5
        ).astype(dtype)

    kinds = cfg.layer_kinds()
    layer_keys = keys[2 : 2 + cfg.n_layers]
    if cfg.scan_layers and cfg.is_homogeneous:
        # vmapped, not stacked from per-layer copies: the stack would hold
        # every layer twice at its peak (2 x 7.9 GB for h2o-danube3-4b)
        params["layers"] = jax.vmap(
            lambda k: _init_layer(k, cfg, kinds[0], dtype, cross=cfg.cross_attention)
        )(layer_keys)
    else:
        params["layers"] = [
            _init_layer(layer_keys[i], cfg, kinds[i], dtype, cross=cfg.cross_attention)
            for i in range(cfg.n_layers)
        ]

    if cfg.n_encoder_layers:
        ekeys = keys[2 + cfg.n_layers : 2 + cfg.n_layers + cfg.n_encoder_layers]
        stacked = [_init_layer(k, cfg, "attn", dtype) for k in ekeys]
        params["enc_layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *stacked)
        params["enc_norm"] = jnp.zeros((cfg.d_model,), dtype)
    return params


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def _attn_apply(cfg: ModelConfig, ap, x, *, positions, causal, lo=None,
                kv_override=None):
    """Full-sequence attention. kv_override: (k_src, kv_positions) for cross.
    ``lo``: the layer's window and rope (``_layer_ops``); None for the
    encoder and cross-attention: no window, the config's plain rope.

    Megatron layout: inside attention the *head* dim carries the model axis
    (seq gathered); the residual stream outside is seq-sharded.  Explicit
    constraints here stop GSPMD from guessing a seq-sharded q through the
    attention chunking reshape (which it can only realize by involuntary
    full rematerialization — replicating the whole tensor).
    """
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,de->bse", x, ap["wq"]).reshape(B, S, cfg.n_heads, hd)
    src = x if kv_override is None else kv_override[0]
    Skv = src.shape[1]
    k = jnp.einsum("bsd,de->bse", src, ap["wk"]).reshape(B, Skv, cfg.n_kv_heads, hd)
    v = jnp.einsum("bsd,de->bse", src, ap["wv"]).reshape(B, Skv, cfg.n_kv_heads, hd)
    # attention parallelization policy: heads over the model axis when they
    # divide it (Megatron); otherwise shard the independent q rows over it
    # (the MQA/few-head case — replicating attention over 16 chips would
    # waste 16x compute).  KV stays gathered in the q-row case.
    ctx = mesh_context()
    tp = ctx.extent(ctx.resolve("model")) if ctx else 1
    head_parallel = tp > 1 and cfg.n_heads % tp == 0
    q_chunk = cfg.attn_q_chunk
    if head_parallel:
        spec = ("batch", None, "model", None)
        q = shard(q, *spec)
        k = shard(k, *spec)
        v = shard(v, *spec)
    else:
        q = shard(q, "batch", "attn_seq", None, None)
        k = shard(k, "batch", None, None, None)
        v = shard(v, "batch", None, None, None)
        if tp > 1:
            q_chunk = 0   # q rows sharded: no q loop (a lax.map would
            #               serialize one device-resident chunk at a time)
    if lo is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        if kv_override is None:
            k = apply_rope(k, positions, cfg.rope_theta)
    else:
        q, k = _rope(q, positions, lo), _rope(k, positions, lo)
    out = chunked_attention(
        q, k, v, causal=causal, window=0 if lo is None else lo["window"],
        chunk=cfg.attn_chunk, q_chunk=q_chunk,
    )
    if head_parallel:
        out = shard(out, "batch", None, "model", None)
    else:
        out = shard(out, "batch", "attn_seq", None, None)
    out = out.reshape(B, S, cfg.n_heads * hd)
    proj = jnp.einsum("bse,ed->bsd", out, ap["wo"])
    # row-parallel epilogue lands sequence-sharded (reduce-scatter, not a
    # full f32 all-reduce — same Megatron-SP pinning as glu_ffn)
    return shard(proj, "batch", "seq", None), (k, v)


def _mlp_apply(cfg: ModelConfig, mp, x):
    """Training's feed-forward. Returns (out, aux)."""
    if cfg.n_experts:
        return moe_ffn(mp, x, top_k=cfg.top_k, capacity_factor=cfg.capacity_factor, act=cfg.act)
    return glu_ffn(x, mp["w_gate"], mp["w_up"], mp["w_down"], cfg.act), 0.0


def _mlp_serve(cfg: ModelConfig, mp, x, token_mask=None, layer=None):
    """Serving's feed-forward: the dropless expert layer over the experts
    held, or the dense one.  Returns (out, int32 [2] expert counts:
    routed pairs on held experts, held experts hit; None when dense).
    With ``layer``, ``mp``'s expert matrices are every layer's, read in
    place."""
    if cfg.n_experts:
        return expert_layer(mp, x, top_k=cfg.top_k,
                            first_expert=cfg.first_expert, act=cfg.act,
                            token_mask=token_mask, layer=layer)
    return glu_ffn(x, mp["w_gate"], mp["w_up"], mp["w_down"], cfg.act), None


def _layer_ops(cfg: ModelConfig) -> dict:
    """What differs between layers beside their weights, stacked ``[L, ...]``
    as operands of the layer scan: each layer's attention window (0: the
    whole context), and, for rope archs, its inverse frequencies
    ``[hd/2]`` and the scale of its cos and sin (YaRN's attention factor;
    1 for plain rope)."""
    ops = {"window": jnp.asarray(cfg.layer_windows(), jnp.int32)}
    if cfg.rope_theta > 0:
        hd = cfg.resolved_head_dim
        yarn = np.asarray(cfg.layer_yarn(), np.int32)
        tables = [rope_inv_freq(hd, cfg.rope_theta)]
        scales = [1.0]
        if yarn.any():
            tables.append(yarn_inv_freq(hd, cfg.rope_theta, cfg.yarn_factor,
                                        cfg.yarn_original_max_position))
            scales.append(yarn_scale(cfg.yarn_factor))
        ops["inv_freq"] = jnp.stack(tables)[yarn]
        ops["rope_scale"] = jnp.asarray(scales, jnp.float32)[yarn]
    return ops


def _layer_op(ops: dict, i: int) -> dict:
    """Layer ``i``'s operands (the unscanned layer loop)."""
    return {k: v[i] for k, v in ops.items()}


def _rope(x, positions, lo):
    """The layer's rope (``lo`` from ``_layer_ops``); none without tables."""
    if "inv_freq" not in lo:
        return x
    return apply_rope(x, positions, inv_freq=lo["inv_freq"], scale=lo["rope_scale"])


def _block_train(cfg: ModelConfig, lp, kind: str, x, *, positions, lo=None,
                 enc=None, causal=True):
    """One residual block, full-sequence (train/prefill). Returns (x, aux)."""
    aux = 0.0
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if kind == "ssm":
        return x + mamba_block(lp["ssm"], h), aux
    if kind == "attn":
        mix, _ = _attn_apply(cfg, lp["attn"], h, positions=positions,
                             causal=causal, lo=lo)
    else:  # rglru
        mix = rglru_block(lp["rnn"], h)
    if cfg.parallel_block:
        mlp_out, aux = _mlp_apply(cfg, lp["mlp"], h)
        x = x + mix + mlp_out
    else:
        x = x + mix
        if enc is not None:
            hx = rms_norm(x, lp["ln_x"], cfg.norm_eps)
            xo, _ = _attn_apply(
                cfg, lp["xattn"], hx,
                positions=jnp.arange(hx.shape[1]),
                causal=False, kv_override=(enc, None),
            )
            x = x + xo
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        mlp_out, aux = _mlp_apply(cfg, lp["mlp"], h2)
        x = x + mlp_out
    return x, aux


# ---------------------------------------------------------------------------
# embedding / logits
# ---------------------------------------------------------------------------

def _embed(cfg: ModelConfig, params, tokens, pos_offset=None):
    x = params["embed"][tokens]
    if cfg.tie_embeddings:  # gemma-family normalizes the tied embedding
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if cfg.rope_theta <= 0:  # whisper-style absolute sinusoidal positions
        S = x.shape[1]
        if pos_offset is None:
            x = x + sinusoidal_positions(S, cfg.d_model, x.dtype)[None]
        else:
            tab = sinusoidal_positions(1, cfg.d_model, x.dtype)  # freq basis
            # single-position embedding at pos_offset (decode)
            half = cfg.d_model // 2
            freqs = jnp.exp(
                -math.log(10_000.0) * jnp.arange(half, dtype=jnp.float32) / max(half - 1, 1)
            )
            ang = pos_offset.astype(jnp.float32)[..., None] * freqs
            pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(x.dtype)
            # pos_offset is a scalar (shared decode position) or [B]
            # (per-slot continuous batching)
            x = x + (pe[None, None, :] if pe.ndim == 1 else pe[:, None, :])
    return x


def _logits(cfg: ModelConfig, params, x):
    w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    return jnp.einsum("...d,dv->...v", x, w).astype(jnp.float32)


def _encode(cfg: ModelConfig, params, frames):
    """Whisper encoder over precomputed frame embeddings (stub frontend)."""
    x = frames + sinusoidal_positions(frames.shape[1], cfg.d_model, frames.dtype)[None]
    pos = jnp.arange(frames.shape[1])

    def f(x, lp):
        x, _ = _block_train(cfg, lp, "attn", x, positions=pos, causal=False)
        return x, None

    x, _ = jax.lax.scan(f, x, params["enc_layers"])
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# forward (training)
# ---------------------------------------------------------------------------

def forward(cfg: ModelConfig, params, batch: dict, *, remat: bool = False):
    """Training forward. batch: tokens [B,S] (+ image_embeds | frames).
    Returns (logits [B, S_total, padded_vocab] fp32, aux_loss)."""
    tokens = batch["tokens"]
    x = _embed(cfg, params, tokens)

    enc = None
    if cfg.frontend == "vision" and "image_embeds" in batch:
        x = jnp.concatenate([batch["image_embeds"].astype(x.dtype), x], axis=1)
    if cfg.frontend == "audio":
        enc = _encode(cfg, params, batch["frames"])

    S = x.shape[1]
    positions = jnp.arange(S)
    kinds = cfg.layer_kinds()
    # residual stream: batch over DP axes, sequence over the model axis when
    # sequence-parallel activations are enabled (Megatron-SP; saves the remat
    # carries — see DESIGN.md §9). Dropped automatically when S % tp != 0.
    x = shard(x, "batch", "seq", None)

    ops = _layer_ops(cfg)
    if cfg.scan_layers and cfg.is_homogeneous:
        kind = kinds[0]

        def body(carry, inp):
            x, aux = carry
            lp, lo = inp
            x, a = _block_train(cfg, lp, kind, x, positions=positions,
                                lo=lo, enc=enc)
            return (shard(x, "batch", "seq", None), aux + a), None

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   (params["layers"], ops))
    else:
        aux = 0.0
        for i, (lp, kind) in enumerate(zip(params["layers"], kinds)):
            blk = partial(
                _block_train, cfg, lp, kind,
                positions=positions, lo=_layer_op(ops, i), enc=enc,
            )
            if remat:
                blk = jax.checkpoint(blk, prevent_cse=False)
            x, a = blk(x)
            x = shard(x, "batch", "seq", None)
            aux = aux + a

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _logits(cfg, params, x), aux


# ---------------------------------------------------------------------------
# KV / state caches
# ---------------------------------------------------------------------------

def _attn_cache_len(cfg: ModelConfig, max_len: int) -> int:
    """Slots of an attention layer's (ring) cache: the widest window, or
    ``max_len`` where some layer attends over the whole context."""
    windows = cfg.layer_windows()
    return max_len if 0 in windows else min(max_len, max(windows))


def _layer_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int, dtype,
                 per_slot: bool = False):
    if kind == "ssm":
        return init_mamba_cache(cfg, batch, dtype)
    if kind == "rglru":
        return init_rglru_cache(cfg, batch, dtype)
    C = _attn_cache_len(cfg, max_len)
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, C, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, C, cfg.n_kv_heads, hd), dtype),
        "pos": jnp.full((batch, C) if per_slot else (C,), -1, jnp.int32),
    }


def init_cache(cfg: ModelConfig, batch: int, max_len: int, *, per_slot: bool = False) -> dict:
    """KV/state cache for ``batch`` sequences of up to ``max_len`` tokens.

    ``per_slot=True`` is the continuous-batching layout: every batch row is
    an independent request *slot* with its own decode position (``len`` is
    ``[batch]``, attention position tables are ``[batch, C]``), so rows at
    different depths decode in one step and free slots are re-filled via
    :func:`cache_insert_slot` / :func:`cache_evict_slot`.
    """
    dtype = cfg.dtype
    kinds = cfg.layer_kinds()
    if cfg.scan_layers and cfg.is_homogeneous:
        per = [_layer_cache(cfg, kinds[i], batch, max_len, dtype, per_slot)
               for i in range(cfg.n_layers)]
        layers = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
    else:
        layers = [_layer_cache(cfg, k, batch, max_len, dtype, per_slot) for k in kinds]
    shape = (batch,) if per_slot else ()
    cache: dict[str, Any] = {"len": jnp.zeros(shape, jnp.int32), "layers": layers}
    if cfg.frontend == "audio":
        cache["enc"] = jnp.zeros((batch, cfg.encoder_len, cfg.d_model), dtype)
    return cache


def _write_prefill(lc, k, v):
    """Write full-sequence K/V [B,S,...] into a (possibly ring) cache."""
    C = lc["k"].shape[1]
    S = k.shape[1]
    take = min(S, C)
    pos = jnp.arange(S - take, S)
    slots = pos % C
    lc = dict(lc)
    lc["k"] = lc["k"].at[:, slots].set(k[:, -take:])
    lc["v"] = lc["v"].at[:, slots].set(v[:, -take:])
    if lc["pos"].ndim == 2:   # per-slot table: broadcast over the batch rows
        lc["pos"] = lc["pos"].at[:, slots].set(pos)
    else:
        lc["pos"] = lc["pos"].at[slots].set(pos)
    return lc


def _cache_batch_axis(cfg: ModelConfig) -> int:
    """Leading axis index of the batch/slot dim in cache leaves (stacked
    homogeneous layouts carry the layer dim first)."""
    return 1 if (cfg.scan_layers and cfg.is_homogeneous) else 0


def cache_insert_slot(cfg: ModelConfig, cache: dict, sub: dict, slot) -> dict:
    """Install a single-request cache (``init_cache(cfg, 1, ..., per_slot=True)``
    filled by :func:`prefill`) into row ``slot`` of a shared per-slot cache.

    Overwrites the slot's K/V, position table, and recurrent state wholesale,
    so whatever the previous occupant (or an idle slot's garbage decode
    steps) left behind is evicted by construction.
    """
    ax = _cache_batch_axis(cfg)

    def ins(dst, src):
        if ax == 1:
            return dst.at[:, slot].set(src[:, 0])
        return dst.at[slot].set(src[0])

    cache = dict(cache)
    cache["layers"] = jax.tree.map(ins, cache["layers"], sub["layers"])
    cache["len"] = cache["len"].at[slot].set(sub["len"][0])
    return cache


def cache_evict_slot(cfg: ModelConfig, cache: dict, slot) -> dict:
    """Free row ``slot``: position tables go to -1 (attention masks every
    cache entry out) and the slot's length resets.  K/V and recurrent state
    are left in place — they are unreachable once the positions are cleared
    and are overwritten by the next :func:`cache_insert_slot`."""
    ax = _cache_batch_axis(cfg)

    def ev(layers):
        if not isinstance(layers, dict) or "pos" not in layers:
            return layers
        lc = dict(layers)
        lc["pos"] = (lc["pos"].at[:, slot].set(-1) if ax == 1
                     else lc["pos"].at[slot].set(-1))
        return lc

    cache = dict(cache)
    if isinstance(cache["layers"], list):
        cache["layers"] = [ev(lc) for lc in cache["layers"]]
    else:
        cache["layers"] = ev(cache["layers"])
    cache["len"] = cache["len"].at[slot].set(0)
    return cache


def _block_decode(cfg: ModelConfig, lp, kind: str, x, lc, *, q_pos, lo, enc=None):
    """Single-token block step. x: [B,1,D]. Returns (x, new layer cache)."""
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if kind == "ssm":
        out, lc = mamba_decode_step(lp["ssm"], h, lc)
        return x + out, lc
    if kind == "rglru":
        mix, lc = rglru_decode_step(lp["rnn"], h, lc)
    else:
        ap = lp["attn"]
        B = x.shape[0]
        hd = cfg.resolved_head_dim
        q = jnp.einsum("bsd,de->bse", h, ap["wq"]).reshape(B, 1, cfg.n_heads, hd)
        k = jnp.einsum("bsd,de->bse", h, ap["wk"]).reshape(B, 1, cfg.n_kv_heads, hd)
        v = jnp.einsum("bsd,de->bse", h, ap["wv"]).reshape(B, 1, cfg.n_kv_heads, hd)
        pos_arr = q_pos[:, None] if q_pos.ndim else q_pos[None]
        q, k = _rope(q, pos_arr, lo), _rope(k, pos_arr, lo)
        C = lc["k"].shape[1]
        lc = dict(lc)
        if q_pos.ndim:
            # continuous batching: each row writes at its own ring slot
            slots = q_pos % C
            rows = jnp.arange(B)
            lc["k"] = lc["k"].at[rows, slots].set(k[:, 0])
            lc["v"] = lc["v"].at[rows, slots].set(v[:, 0])
            lc["pos"] = lc["pos"].at[rows, slots].set(q_pos)
        else:
            slot = q_pos % C
            lc["k"] = jax.lax.dynamic_update_index_in_dim(lc["k"], k[:, 0], slot, 1)
            lc["v"] = jax.lax.dynamic_update_index_in_dim(lc["v"], v[:, 0], slot, 1)
            lc["pos"] = jax.lax.dynamic_update_index_in_dim(lc["pos"], q_pos, slot, 0)
        out = decode_attention(
            q, lc["k"], lc["v"], lc["pos"], q_pos, window=lo["window"]
        )
        mix = jnp.einsum("bse,ed->bsd", out.reshape(B, 1, -1), ap["wo"])
    if cfg.parallel_block:
        mlp_out, _ = _mlp_serve(cfg, lp["mlp"], h)
        return x + mix + mlp_out, lc
    x = x + mix
    if enc is not None:
        hx = rms_norm(x, lp["ln_x"], cfg.norm_eps)
        xo, _ = _attn_apply(
            cfg, lp["xattn"], hx, positions=q_pos[None, None],
            causal=False, kv_override=(enc, None),
        )
        x = x + xo
    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    mlp_out, _ = _mlp_serve(cfg, lp["mlp"], h2)
    return x + mlp_out, lc


def prefill(cfg: ModelConfig, params, batch: dict, cache: dict):
    """Run the full prompt, fill the cache, return last-position logits.

    ``batch["valid_len"]`` (optional scalar int32) marks the prompt as
    right-padded: only the first ``valid_len`` tokens are real.  Logits come
    from position ``valid_len - 1``, the cache length is ``valid_len``, and
    position-table entries past it are cleared to -1 so later decode steps
    mask the padded K/V out.  This is what lets the serving engines bucket
    prompt lengths to a handful of compiled shapes (attention-only archs:
    recurrent state and MoE capacity routing would absorb the pad tokens).
    """
    tokens = batch["tokens"]
    valid_len = batch.get("valid_len")
    if valid_len is not None and any(k != "attn" for k in cfg.layer_kinds()):
        raise ValueError("valid_len-masked prefill requires attention-only archs")
    x = _embed(cfg, params, tokens)
    enc = None
    if cfg.frontend == "vision" and "image_embeds" in batch:
        x = jnp.concatenate([batch["image_embeds"].astype(x.dtype), x], axis=1)
    if cfg.frontend == "audio":
        enc = _encode(cfg, params, batch["frames"])
        cache = dict(cache)
        cache["enc"] = enc

    S = x.shape[1]
    positions = jnp.arange(S)
    kinds = cfg.layer_kinds()
    x = shard(x, "batch", "seq", None)
    # padding routes to no expert
    real = (None if valid_len is None else
            jnp.broadcast_to(positions < valid_len, x.shape[:2]))

    def run_block(x, lp, lc, kind, lo):
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        if kind == "ssm":
            # full-seq scan, then regenerate the decode state via step-free
            # trailing state (mamba_block keeps h internal; recompute final
            # state with the chunked scan's carry):
            out, lc = _mamba_prefill(lp["ssm"], h, lc)
            return x + out, lc
        if kind == "rglru":
            out, lc = _rglru_prefill(lp["rnn"], h, lc)
            mix = out
        else:
            mix, (k, v) = _attn_apply(
                cfg, lp["attn"], h, positions=positions, causal=True, lo=lo,
            )
            lc = _write_prefill(lc, k, v)
        if cfg.parallel_block:
            mlp_out, _ = _mlp_serve(cfg, lp["mlp"], h, real)
            return x + mix + mlp_out, lc
        x = x + mix
        if enc is not None:
            hx = rms_norm(x, lp["ln_x"], cfg.norm_eps)
            xo, _ = _attn_apply(
                cfg, lp["xattn"], hx, positions=positions,
                causal=False, kv_override=(enc, None),
            )
            x = x + xo
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        mlp_out, _ = _mlp_serve(cfg, lp["mlp"], h2, real)
        return shard(x + mlp_out, "batch", "seq", None), lc

    ops = _layer_ops(cfg)
    if cfg.scan_layers and cfg.is_homogeneous:
        kind = kinds[0]

        def body(x, inp):
            lp, lc, lo = inp
            x, lc = run_block(x, lp, lc, kind, lo)
            return x, lc

        x, new_layers = jax.lax.scan(body, x, (params["layers"], cache["layers"], ops))
    else:
        new_layers = []
        for i, (lp, lc, kind) in enumerate(zip(params["layers"], cache["layers"], kinds)):
            x, lc = run_block(x, lp, lc, kind, _layer_op(ops, i))
            new_layers.append(lc)

    if valid_len is not None:
        valid_len = jnp.asarray(valid_len, jnp.int32)

        def mask_tbl(lc):
            if isinstance(lc, dict) and "pos" in lc:
                lc = dict(lc)
                lc["pos"] = jnp.where(lc["pos"] < valid_len, lc["pos"], -1)
            return lc

        new_layers = ([mask_tbl(lc) for lc in new_layers]
                      if isinstance(new_layers, list) else mask_tbl(new_layers))

    cache = dict(cache)
    cache["layers"] = new_layers
    # scalar for the shared-position layout, [B] for per-slot caches
    if valid_len is None:
        cache["len"] = jnp.full_like(cache["len"], S)
        x_last = x[:, -1]
    else:
        cache["len"] = jnp.broadcast_to(valid_len, cache["len"].shape)
        x_last = jax.lax.dynamic_index_in_dim(x, valid_len - 1, axis=1,
                                              keepdims=False)
    x = rms_norm(x_last, params["final_norm"], cfg.norm_eps)
    return _logits(cfg, params, x), cache


def _mamba_prefill(mp, h, lc):
    """Mamba over the full prompt, returning output and final decode state."""
    from .layers import causal_conv1d
    from .mamba import ssm_scan_fused

    B, L, _ = h.shape
    xz = jnp.einsum("bld,de->ble", h, mp["in_proj"])
    xpart, res = jnp.split(xz, 2, axis=-1)
    xconv, _ = causal_conv1d(xpart, mp["conv_w"])
    xconv = jax.nn.silu(xconv + mp["conv_b"])
    di, st = mp["A_log"].shape
    y, h_last = ssm_scan_fused(mp, xconv, jnp.zeros((B, di, st), jnp.float32))
    y = y + mp["D"] * xconv.astype(jnp.float32)
    y = y * jax.nn.silu(res.astype(jnp.float32))
    out = jnp.einsum("bld,de->ble", y.astype(h.dtype), mp["out_proj"])
    K = mp["conv_w"].shape[0]
    new_cache = {"h": h_last, "conv": xpart[:, -(K - 1):, :]}
    return out, new_cache


def _rglru_prefill(rp, h, lc):
    from .griffin import _rglru_gates
    from .layers import causal_conv1d, linear_recurrence_chunked

    B = h.shape[0]
    y_branch = jax.nn.gelu(jnp.einsum("bld,dr->blr", h, rp["w_y"]))
    x_branch = jnp.einsum("bld,dr->blr", h, rp["w_x"])
    xc, _ = causal_conv1d(x_branch, rp["conv_w"])
    xc = xc + rp["conv_b"]
    a, b = _rglru_gates(rp, xc)
    hs, h_last = linear_recurrence_chunked(a, b, jnp.zeros((B, a.shape[-1]), jnp.float32))
    out = jnp.einsum("blr,rd->bld", (hs.astype(h.dtype) * y_branch), rp["w_o"])
    K = rp["conv_w"].shape[0]
    new_cache = {"h": h_last, "conv": x_branch[:, -(K - 1):, :]}
    return out, new_cache


def decode_step(cfg: ModelConfig, params, tokens, cache: dict):
    """One decode step. tokens: [B, 1]. Returns (logits [B, Vp], new cache)."""
    q_pos = cache["len"]
    x = _embed(cfg, params, tokens, pos_offset=q_pos)
    x = shard(x, "batch", None, None)
    enc = cache.get("enc")
    kinds = cfg.layer_kinds()

    ops = _layer_ops(cfg)
    if cfg.scan_layers and cfg.is_homogeneous:
        kind = kinds[0]

        def body(x, inp):
            lp, lc, lo = inp
            x, lc = _block_decode(cfg, lp, kind, x, lc, q_pos=q_pos, lo=lo, enc=enc)
            return x, lc

        x, new_layers = jax.lax.scan(body, x, (params["layers"], cache["layers"], ops))
    else:
        new_layers = []
        for i, (lp, lc, kind) in enumerate(zip(params["layers"], cache["layers"], kinds)):
            x, lc = _block_decode(cfg, lp, kind, x, lc, q_pos=q_pos,
                                  lo=_layer_op(ops, i), enc=enc)
            new_layers.append(lc)

    cache = dict(cache)
    cache["layers"] = new_layers
    cache["len"] = cache["len"] + 1
    x = rms_norm(x[:, -1], params["final_norm"], cfg.norm_eps)
    return _logits(cfg, params, x), cache


# ---------------------------------------------------------------------------
# paged KV cache: global page pool + per-request page tables
# ---------------------------------------------------------------------------
#
# Layout: one page pool {"k": [P, L, ps, Hkv, W], "v": ...} — a physical
# page holds its ps tokens for every layer, whether or not the arch scans
# its layers — a page table [B, n_pt] mapping each slot's logical page j to
# a physical page id (-1 = unmapped), and per-slot lengths [B].  The logical
# KV position of table entry (j, t) is j*ps + t, so a request's pages
# reconstruct its linear cache without it ever existing contiguously — one
# short request pins ceil(len/ps) pages instead of a full max_len slot, and
# requests sharing a prompt prefix can map the *same* physical pages
# (serve/paged.py owns refcounts + CoW).
#
# W is the head size rounded up to the TPU's 128 lanes (zeros past it): the
# TPU's default layout of the pool is then row-major, which the paged
# decode kernel reads as it is.  With a head size off the lanes (danube3's
# 120), or with the layer axis leading, the default layout puts the page
# axis minor-most and every kernel call would first copy the whole pool into
# row-major order; row-major storage pads a 120-wide row to 128 lanes
# anyway, so W costs no memory over it.
#
# The page table and lengths are host-managed (numpy in the serving engine,
# passed in as int32 arrays per step).  The decode and chunk graphs only
# read the pool, in place by (page, layer), and return the K/V they computed;
# the engine writes those with paged_install_decode / paged_insert_chunk,
# which update the pool it donates — no program moves the pool whole.
_LANES = 128


def paged_supported(cfg: ModelConfig) -> bool:
    """Paged serving covers decoder-only, attention-only, rope archs: SSM /
    RG-LRU carry recurrent state that has no paged analogue, and encoder
    frontends are not served continuously in the first place."""
    return (not cfg.frontend and not cfg.n_encoder_layers
            and cfg.rope_theta > 0
            and all(k == "attn" for k in cfg.layer_kinds()))


def _paged_stacked(cfg: ModelConfig) -> bool:
    return cfg.scan_layers and cfg.is_homogeneous


def init_paged_cache(cfg: ModelConfig, batch: int, max_len: int, *,
                     n_pages: int, page_size: int) -> dict:
    """Paged KV cache for ``batch`` request slots over a ``n_pages``-page
    global pool.  ``table``/``len`` come back as numpy (host-managed by the
    allocator); ``pages`` are the device pool of every layer."""
    if not paged_supported(cfg):
        raise ValueError("paged KV cache requires a decoder-only "
                         "attention-only rope arch "
                         f"(got kinds={cfg.layer_kinds()}, frontend={cfg.frontend!r})")
    n_pt = -(-max_len // page_size)
    lanes = -(-cfg.resolved_head_dim // _LANES) * _LANES
    shape = (n_pages, cfg.n_layers, page_size, cfg.n_kv_heads, lanes)
    return {
        "len": np.zeros((batch,), np.int32),
        "table": np.full((batch, n_pt), -1, np.int32),
        "pages": {"k": jnp.zeros(shape, cfg.dtype),
                  "v": jnp.zeros(shape, cfg.dtype)},
    }


def alloc_page(cache: dict, slot: int, logical_idx: int, page: int) -> dict:
    """Map physical ``page`` at logical index ``logical_idx`` of ``slot``'s
    page table (host-side bookkeeping; the pool allocator picks ``page``)."""
    table = np.asarray(cache["table"]).copy()
    if table[slot, logical_idx] >= 0:
        raise ValueError(f"slot {slot} logical page {logical_idx} already "
                         f"mapped to {table[slot, logical_idx]}")
    table[slot, logical_idx] = page
    return {**cache, "table": table}


def free_pages(cache: dict, slot: int) -> tuple[dict, list[int]]:
    """Unmap every page of ``slot`` and reset its length.  Returns the new
    cache and the freed physical page ids (the allocator decides whether
    they return to the free list or stay as cold prefix cache)."""
    table = np.asarray(cache["table"]).copy()
    freed = [int(p) for p in table[slot] if p >= 0]
    table[slot] = -1
    length = np.asarray(cache["len"]).copy()
    length[slot] = 0
    return {**cache, "table": table, "len": length}, freed


_EXPERT_MATRICES = ("w_gate", "w_up", "w_down")


def _paged_layers(cfg: ModelConfig, params, x, block):
    """Run ``block(x, lp, layer, lo, stacked) -> (x, (k, v, expert counts))``
    over every layer (``lo``: its ``_layer_ops``), scanned when the arch
    stacks its layers; returns x, the stacked [L, ...] K/V and the counts
    summed over the layers (zeros for a dense arch, whose blocks count
    nothing).  The pool is closed over, never a scan operand:
    each layer reads its pages in place through ``layer``.  So are a
    scanned expert arch's expert matrices (``stacked``, ``lp["mlp"]`` holds
    only the router): a scan operand's slice would copy a layer's experts
    (hundreds of MB) every layer of every step."""
    ops = _layer_ops(cfg)
    if _paged_stacked(cfg):
        layers = params["layers"]
        stacked = None
        if cfg.n_experts:
            stacked = {k: layers["mlp"][k] for k in _EXPERT_MATRICES}
            rest = {k: v for k, v in layers["mlp"].items() if k not in stacked}
            layers = {**layers, "mlp": rest}

        def body(x, inp):
            lp, layer, lo = inp
            return block(x, lp, layer, lo, stacked)

        idx = jnp.arange(cfg.n_layers, dtype=jnp.int32)
        x, (k, v, counts) = jax.lax.scan(body, x, (layers, idx, ops))
    else:
        outs = []
        for layer, lp in enumerate(params["layers"]):
            x, out = block(x, lp, jnp.int32(layer), _layer_op(ops, layer), None)
            outs.append(out)
        k, v, counts = zip(*outs)
        k, v = jnp.stack(k), jnp.stack(v)
        counts = None if counts[0] is None else jnp.stack(counts)
    if counts is None:
        return x, (k, v), jnp.zeros((2,), jnp.int32)
    return x, (k, v), counts.sum(axis=0)


def _paged_mlp(lp, layer, stacked):
    """A paged block's feed-forward weights, and the layer to read of them:
    the scan's slice, or the router's slice beside every layer's experts."""
    if stacked is None:
        return lp["mlp"], None
    return {**lp["mlp"], **stacked}, layer


def paged_decode_step(cfg: ModelConfig, params, tokens, cache: dict):
    """One decode step over the paged cache.  tokens: [B, 1].

    Reads the pool (positions below each row's length) and writes nothing:
    returns (logits [B, Vp], {"k", "v"}: this step's K/V [L, B, Hkv, hd],
    which the engine installs at each row's length with
    :func:`paged_install_decode`; "experts": int32 [2], the routed pairs
    that fell on held experts and the held experts hit, summed over the
    layers).  A row whose page table maps nothing is idle: it routes to no
    expert."""
    from repro.kernels.decode_attention import paged_decode_attention

    q_pos = jnp.asarray(cache["len"], jnp.int32)
    table = jnp.asarray(cache["table"], jnp.int32)
    pages = cache["pages"]
    x = _embed(cfg, params, tokens, pos_offset=q_pos)
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    pos_arr = q_pos[:, None]
    active = (table[:, 0] >= 0)[:, None]

    def block(x, lp, layer, lo, stacked):
        ap = lp["attn"]
        mp, at = _paged_mlp(lp, layer, stacked)
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        q = jnp.einsum("bsd,de->bse", h, ap["wq"]).reshape(B, 1, cfg.n_heads, hd)
        k = jnp.einsum("bsd,de->bse", h, ap["wk"]).reshape(B, 1, cfg.n_kv_heads, hd)
        v = jnp.einsum("bsd,de->bse", h, ap["wv"]).reshape(B, 1, cfg.n_kv_heads, hd)
        q = _rope(q, pos_arr, lo)
        k = _rope(k, pos_arr, lo)[:, 0]
        v = v[:, 0]
        out = paged_decode_attention(q, k, v, pages["k"], pages["v"], layer,
                                     table, q_pos, window=lo["window"])
        mix = jnp.einsum("bse,ed->bsd", out.reshape(B, 1, -1), ap["wo"])
        if cfg.parallel_block:
            mlp_out, counts = _mlp_serve(cfg, mp, h, active, at)
            return x + mix + mlp_out, (k, v, counts)
        x = x + mix
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        mlp_out, counts = _mlp_serve(cfg, mp, h2, active, at)
        return x + mlp_out, (k, v, counts)

    x, (k_new, v_new), counts = _paged_layers(cfg, params, x, block)
    x = rms_norm(x[:, -1], params["final_norm"], cfg.norm_eps)
    return _logits(cfg, params, x), {"k": k_new, "v": v_new, "experts": counts}


def paged_prefill_chunk(cfg: ModelConfig, params, tokens, pages, table_row,
                        start, valid_len, *, page_size: int):
    """One page-aligned prompt chunk for a single request (chunked prefill).

    tokens: [1, T] (right-padded; first ``valid_len`` real), table_row:
    [n_pt] — the request's page-table row, ``start`` — the absolute position
    of tokens[0].  Reads already-computed context K/V from the pool
    (entries at positions < start; the mask is *strict* so stale data in the
    partially-filled tail page never leaks in), computes the chunk's K/V and
    returns it **without writing**: the engine scatters it into the pool
    afterwards (paged_insert_chunk), which keeps this graph free of pool
    writes and lets it run concurrently with the decode step.

    Returns (logits [1, Vp] at position start+valid_len-1,
    k_chunk, v_chunk — [L, T, Hkv, hd] — and the expert counts, int32 [2],
    as :func:`paged_decode_step`'s; padding routes to no expert).
    """
    T = tokens.shape[1]
    start = jnp.asarray(start, jnp.int32)
    valid_len = jnp.asarray(valid_len, jnp.int32)
    table_row = jnp.asarray(table_row, jnp.int32)
    pos = start + jnp.arange(T, dtype=jnp.int32)          # [T]
    x = _embed(cfg, params, tokens)                        # rope: positionless
    hd = cfg.resolved_head_dim
    real = (jnp.arange(T) < valid_len)[None]
    n_pt = table_row.shape[0]
    ps = page_size
    rows = jnp.maximum(table_row, 0)
    idx = jnp.arange(n_pt * ps, dtype=jnp.int32)
    mapped = jnp.repeat(table_row >= 0, ps)
    ctx_pos = jnp.where(mapped & (idx < start), idx, -1)
    kv_pos = jnp.concatenate([ctx_pos, pos])

    def block(x, lp, layer, lo, stacked):
        ap = lp["attn"]
        mp, at = _paged_mlp(lp, layer, stacked)
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        q = jnp.einsum("bsd,de->bse", h, ap["wq"]).reshape(1, T, cfg.n_heads, hd)
        k = jnp.einsum("bsd,de->bse", h, ap["wk"]).reshape(1, T, cfg.n_kv_heads, hd)
        v = jnp.einsum("bsd,de->bse", h, ap["wv"]).reshape(1, T, cfg.n_kv_heads, hd)
        q = _rope(q, pos[None], lo)
        k = _rope(k, pos[None], lo)
        # this layer's pages of the row, gathered in place from the pool
        ctx_k = _layer_pages(pages["k"], rows, layer)[..., :hd]
        ctx_v = _layer_pages(pages["v"], rows, layer)[..., :hd]
        ctx_k = ctx_k.reshape(1, n_pt * ps, cfg.n_kv_heads, hd)
        ctx_v = ctx_v.reshape(1, n_pt * ps, cfg.n_kv_heads, hd)
        k_all = jnp.concatenate([ctx_k, k], axis=1)
        v_all = jnp.concatenate([ctx_v, v], axis=1)
        out = masked_attention(q, k_all, v_all, kv_pos, pos, window=lo["window"])
        mix = jnp.einsum("bse,ed->bsd", out.reshape(1, T, -1), ap["wo"])
        if cfg.parallel_block:
            mlp_out, counts = _mlp_serve(cfg, mp, h, real, at)
            return x + mix + mlp_out, (k[0], v[0], counts)
        x = x + mix
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        mlp_out, counts = _mlp_serve(cfg, mp, h2, real, at)
        return x + mlp_out, (k[0], v[0], counts)

    x, (k_chunk, v_chunk), counts = _paged_layers(cfg, params, x, block)
    x_last = jax.lax.dynamic_index_in_dim(x, valid_len - 1, axis=1, keepdims=False)
    x_last = rms_norm(x_last, params["final_norm"], cfg.norm_eps)
    return _logits(cfg, params, x_last), k_chunk, v_chunk, counts


def _layer_pages(pool, pages, layer):
    """pool[pages, layer]: [..., ps, Hkv, W], gathered as whole rows of the
    pool viewed as [P * L, ps * Hkv, W] — a view that costs nothing in the
    pool's row-major layout, where a gather over two leading axes, or over
    rows of [ps, Hkv, W] with fewer KV heads than a tile has rows (Mellum2's
    4), would have XLA:TPU relayout the whole pool first."""
    P, L, ps, hkv, w = pool.shape
    rows = pool.reshape(P * L, ps * hkv, w)[pages * L + layer]
    return rows.reshape(*rows.shape[:-2], ps, hkv, w)


def _write_tokens(pages, phys, off, k, v):
    """pages[phys[i], :, off[i]] = k[:, i] (and v): token i's K/V of every
    layer ([L, N, Hkv, hd], zero-padded to the pool's lanes); ``phys`` == P
    (out of bounds) drops the write.  Jitted with the pool donated, this
    updates the pool in place."""
    def put(pool, x):
        x = jnp.pad(x.swapaxes(0, 1),
                    [(0, 0)] * 3 + [(0, pool.shape[-1] - x.shape[-1])])
        return pool.at[phys, :, off].set(x, mode="drop")

    return {"k": put(pages["k"], k), "v": put(pages["v"], v)}


def paged_install_decode(pages, table, q_pos, k_new, v_new, *,
                         page_size: int):
    """Write a decode step's K/V ([L, B, Hkv, hd], from
    :func:`paged_decode_step`) at each row's position ``q_pos`` through the
    page table; rows whose page there is unmapped (idle slots) redirect out
    of bounds and are dropped — never a wrapped write into page P-1."""
    table = jnp.asarray(table, jnp.int32)
    q_pos = jnp.asarray(q_pos, jnp.int32)
    P = pages["k"].shape[0]
    phys = jnp.take_along_axis(table, (q_pos // page_size)[:, None], axis=1)[:, 0]
    phys = jnp.where(phys < 0, P, phys)
    return _write_tokens(pages, phys, q_pos % page_size, k_new, v_new)


def paged_insert_chunk(pages, table_row, start, valid_len, k_chunk, v_chunk,
                       *, page_size: int):
    """Scatter a prefill chunk's K/V ([L, T, Hkv, hd]) into the pool through
    the page table.  Padded positions (>= valid_len) and unmapped pages
    redirect out of bounds and are dropped."""
    table_row = jnp.asarray(table_row, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    valid_len = jnp.asarray(valid_len, jnp.int32)
    T = k_chunk.shape[1]
    P = pages["k"].shape[0]
    idx = start + jnp.arange(T, dtype=jnp.int32)
    phys = table_row[idx // page_size]
    drop = (jnp.arange(T) >= valid_len) | (phys < 0)
    phys = jnp.where(drop, P, phys)
    return _write_tokens(pages, phys, idx % page_size, k_chunk, v_chunk)


def paged_copy_page(pages, src, dst):
    """Copy physical page ``src`` onto ``dst`` in every layer's pool
    (copy-on-write: a new request that shares only part of a registered
    page copies it and overwrites from its first divergent token)."""
    return jax.tree.map(lambda a: a.at[dst].set(a[src]), pages)
