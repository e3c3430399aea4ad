"""A test architecture that is not the dense decoder, reached only through
the ``"reference"`` of ``toy_moe.json``: sparse experts behind a router kept
in float32, no shared expert, and windowed attention layers mixed with
full ones by a per-layer key (``layer_types``, as Mellum2's config.json
names them).  Not a published model; plain rotary embedding on every layer.

It borrows the dense reference's primitives (matrix products with the
float8 control, RMSNorm, rotary embedding, embedding and head); the layer,
the weight tree and the counts are its own.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference.dense_gqa import embed, head, head_dim, mm, rms_norm, rope

OWN_KEYS = ("layer_types",)


def shapes(m: dict) -> dict:
    L, d, f, V, E = (m["n_layers"], m["d_model"], m["d_ff"], m["vocab_size"],
                     m["n_experts"])
    hd, H, Hkv = head_dim(m), m["n_heads"], m["n_kv_heads"]
    return {
        "embed": (V, d), "final_norm": (d,), "unembed": (d, V),
        "layers": {
            "ln1": (L, d), "ln2": (L, d),
            "attn": {"wq": (L, d, H * hd), "wk": (L, d, Hkv * hd),
                     "wv": (L, d, Hkv * hd), "wo": (L, H * hd, d)},
            "moe": {"router": jax.ShapeDtypeStruct((L, d, E), jnp.float32),
                    "w_gate": (L, E, d, f), "w_up": (L, E, d, f),
                    "w_down": (L, E, f, d)},
        },
    }


def matmul_params(m: dict) -> int:
    """Attention, the router and the ``top_k`` experts a token goes
    through, in every layer; and the head."""
    d, f, hd, H = m["d_model"], m["d_ff"], head_dim(m), m["n_heads"]
    attn = d * hd * (H + 2 * m["n_kv_heads"]) + H * hd * d
    moe = d * m["n_experts"] + m["top_k"] * 3 * d * f
    return m["n_layers"] * (attn + moe) + d * m["vocab_size"]


def attention_windows(m: dict) -> list:
    return [m["sliding_window"] if t == "sliding_attention" else None
            for t in m["layer_types"]]


def _attention(q, k, v, pos, window, mode):
    """Causal attention; ``window`` may be traced, 0 for the whole context."""
    B, S, H, hd = q.shape
    G = H // k.shape[2]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    keep = pos[None, :] <= pos[:, None]
    keep &= (window <= 0) | (pos[None, :] > pos[:, None] - window)
    s = mm("bqhd,bkhd->bhqk", q, k, mode) / math.sqrt(hd)
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return mm("bhqk,bkhd->bqhd", p, v, mode).reshape(B, S, H * hd)


def _experts(m: dict, e: dict, h, mode):
    """Softmax router, the top ``top_k`` renormalised, each expert a SwiGLU;
    every expert computed, the unrouted weighted by 0."""
    probs = jax.nn.softmax(mm("bsd,de->bse", h, e["router"], mode), axis=-1)
    top, idx = jax.lax.top_k(probs, m["top_k"])
    top = top / top.sum(axis=-1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(idx, m["n_experts"]) * top[..., None],
                    axis=-2)
    g = jax.nn.silu(mm("bsd,edf->bsef", h, e["w_gate"], mode))
    u = mm("bsd,edf->bsef", h, e["w_up"], mode)
    y = mm("bsef,efd->bsed", g * u, e["w_down"], mode)
    return jnp.einsum("bsed,bse->bsd", y, gates)


def block(m: dict, lp: dict, i, x, pos, mode: str = "f32"):
    """Layer ``i`` (weights ``lp``; ``i`` may be traced). x: [B, S, d]."""
    B, S, _ = x.shape
    H, Hkv, hd = m["n_heads"], m["n_kv_heads"], head_dim(m)
    window = jnp.asarray([w or 0 for w in attention_windows(m)])[i]
    a = lp["attn"]
    h = rms_norm(x, lp["ln1"], m["norm_eps"])
    q = mm("bsd,de->bse", h, a["wq"], mode).reshape(B, S, H, hd)
    k = mm("bsd,de->bse", h, a["wk"], mode).reshape(B, S, Hkv, hd)
    v = mm("bsd,de->bse", h, a["wv"], mode).reshape(B, S, Hkv, hd)
    q, k = rope(q, pos, m["rope_theta"]), rope(k, pos, m["rope_theta"])
    x = x + mm("bse,ed->bsd", _attention(q, k, v, pos, window, mode),
               a["wo"], mode)
    return x + _experts(m, lp["moe"], rms_norm(x, lp["ln2"], m["norm_eps"]),
                        mode)


def forward(m: dict, params, tokens, mode: str = "f32"):
    """Logits [B, S, V] of token ids [B, S]."""
    pos = jnp.arange(tokens.shape[1])
    x = embed(params, tokens)
    for i in range(m["n_layers"]):
        x = block(m, jax.tree.map(lambda a: a[i], params["layers"]), i, x,
                  pos, mode)
    return head(m, params, x, mode)
