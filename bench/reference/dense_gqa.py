"""Plain reference of a dense decoder with grouped-query attention, as the
Llama family publishes it: RMSNorm, rotary embedding on the two halves of
each head, causal (optionally windowed) softmax attention, SwiGLU
feed-forward, an untied output head.  Straight ``jax.numpy`` in float32 at
``Precision.HIGHEST``; nothing of the program under test is imported.

This module is the architecture that a configuration file names under
``"reference"``: besides the forward pass it gives the benchmark the weight
tree (``shapes``), the weights a token multiplies through
(``matmul_params``) and each attention layer's window
(``attention_windows``).  Weights are read in the layout the benchmark
makes them (``bench/harness/weights.py``).  One departure from the
published form: a norm's gain is stored as ``scale`` and applied as
``1 + scale``.

``mode="fp8"`` is the control: every matrix product takes its operands
rounded to float8 e4m3 (a scale per row of the left operand and per column
of the right one), and its backward products the cotangent rounded to
e5m2: the precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def head_dim(m: dict) -> int:
    return int(m.get("head_dim") or m["d_model"] // m["n_heads"])


def shapes(m: dict) -> dict:
    """The weight tree: each leaf's shape, layers stacked on a leading axis."""
    L, d, f, V = m["n_layers"], m["d_model"], m["d_ff"], m["vocab_size"]
    hd, H, Hkv = head_dim(m), m["n_heads"], m["n_kv_heads"]
    return {
        "embed": (V, d), "final_norm": (d,), "unembed": (d, V),
        "layers": {
            "ln1": (L, d), "ln2": (L, d),
            "attn": {"wq": (L, d, H * hd), "wk": (L, d, Hkv * hd),
                     "wv": (L, d, Hkv * hd), "wo": (L, H * hd, d)},
            "mlp": {"w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d)},
        },
    }


def matmul_params(m: dict) -> int:
    """Weights one token multiplies through: q, k, v and o, the SwiGLU's
    three matrices in every layer, and the head."""
    d, f, hd = m["d_model"], m["d_ff"], head_dim(m)
    attn = d * hd * (m["n_heads"] + 2 * m["n_kv_heads"]) + m["n_heads"] * hd * d
    return m["n_layers"] * (attn + 3 * d * f) + d * m["vocab_size"]


def attention_windows(m: dict) -> list:
    """Each attention layer's window (None: the whole context)."""
    return [m.get("sliding_window")] * m["n_layers"]


def _round(a, axis, dtype, top: float):
    """``a`` rounded to ``dtype``, scaled so that its largest magnitude along
    ``axis`` (every axis when None) meets the type's largest number."""
    s = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True), 1e-30) / top
    return (a / s).astype(dtype).astype(jnp.float32) * s


def _axes(spec: str):
    ins, _ = spec.split("->")
    sa, sb = ins.split(",")
    both = set(sa) & set(sb)
    return (tuple(i for i, c in enumerate(sa) if c in both),
            tuple(i for i, c in enumerate(sb) if c in both))


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm_f8(spec: str, a, b):
    return _mm_f8_fwd(spec, a, b)[0]


def _mm_f8_fwd(spec, a, b):
    ax_a, ax_b = _axes(spec)
    qa = _round(a, ax_a, jnp.float8_e4m3fn, 448.0)
    qb = _round(b, ax_b, jnp.float8_e4m3fn, 448.0)
    return _einsum(spec, qa, qb), (qa, qb)


def _mm_f8_bwd(spec, res, ct):
    """The backward products in float8 too: the cotangent rounded to e5m2."""
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: _einsum(spec, x, y), qa, qb)
    return vjp(_round(ct, None, jnp.float8_e5m2, 57344.0))


_mm_f8.defvjp(_mm_f8_fwd, _mm_f8_bwd)


def mm(spec: str, a, b, mode: str):
    """``einsum(spec, a, b)`` in float32.  ``mode="fp8"``: the operands are
    rounded to e4m3 along their contracted axes, and on the backward pass
    the cotangent to e5m2, as float8 training computes."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if mode == "fp8":
        return _mm_f8(spec, a, b)
    if mode != "f32":
        raise ValueError(f"unknown mode {mode!r}")
    return _einsum(spec, a, b)


def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * r * (1.0 + scale.astype(jnp.float32))


def rope(x, pos, theta):
    """x: [..., S, H, hd]; pos: [S]."""
    half = x.shape[-1] // 2
    inv = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, pos, window, mode: str):
    """Causal softmax attention, one key/value head (and the query heads that
    read it) at a time.  q: [B, S, H, hd]; k, v: [B, S, Hkv, hd]."""
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    G = H // Hkv                          # query head h reads kv head h // G
    qg = q.reshape(B, S, Hkv, G, hd).transpose(2, 0, 1, 3, 4)
    keep = pos[None, :] <= pos[:, None]
    if window:
        keep &= pos[None, :] > pos[:, None] - window

    def one(args):
        qh, kh, vh = args                 # [B, S, G, hd], [B, S, hd] x 2
        s = mm("bqgd,bkd->bgqk", qh, kh, mode) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return mm("bgqk,bkd->bqgd", p, vh, mode)

    o = jax.lax.map(one, (qg, k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)))
    return o.transpose(1, 2, 0, 3, 4).reshape(B, S, H * hd)


def block(m: dict, lp: dict, i, x, pos, mode: str = "f32"):
    """Decoder layer ``i`` (weights ``lp``) over a batch of sequences; every
    layer is alike here, so ``i`` is not read.  x: [B, S, d] float32."""
    B, S, _ = x.shape
    H, Hkv = m["n_heads"], m["n_kv_heads"]
    hd = head_dim(m)
    a = lp["attn"]
    h = rms_norm(x, lp["ln1"], m["norm_eps"])
    q = mm("bsd,de->bse", h, a["wq"], mode).reshape(B, S, H, hd)
    k = mm("bsd,de->bse", h, a["wk"], mode).reshape(B, S, Hkv, hd)
    v = mm("bsd,de->bse", h, a["wv"], mode).reshape(B, S, Hkv, hd)
    q, k = rope(q, pos, m["rope_theta"]), rope(k, pos, m["rope_theta"])
    o = attention(q, k, v, pos, m.get("sliding_window"), mode)
    x = x + mm("bse,ed->bsd", o, a["wo"], mode)
    f = lp["mlp"]
    h2 = rms_norm(x, lp["ln2"], m["norm_eps"])
    g = jax.nn.silu(mm("bsd,df->bsf", h2, f["w_gate"], mode))
    u = mm("bsd,df->bsf", h2, f["w_up"], mode)
    return x + mm("bsf,fd->bsd", g * u, f["w_down"], mode)


def embed(params, tokens):
    return params["embed"][tokens].astype(jnp.float32)


def head(m: dict, params, x, mode: str = "f32"):
    """Logits over the vocabulary. x: [..., d]."""
    x = rms_norm(x, params["final_norm"], m["norm_eps"])
    lg = mm("nd,dv->nv", x.reshape(-1, x.shape[-1]), params["unembed"], mode)
    return lg.reshape(*x.shape[:-1], -1)[..., :m["vocab_size"]]


def layer(params, i):
    """Layer ``i``'s weights out of the stacked layers."""
    return jax.tree.map(lambda a: a[i], params["layers"])


def forward(m: dict, params, tokens, mode: str = "f32"):
    """Logits [B, S, V] of token ids [B, S] (whole model at once: for small
    sizes and the training reference)."""
    pos = jnp.arange(tokens.shape[1])
    x = embed(params, tokens)
    for i in range(m["n_layers"]):
        x = block(m, layer(params, i), i, x, pos, mode)
    return head(m, params, x, mode)


def loss(m: dict, params, tokens, labels, mode: str = "f32"):
    """Mean next-token cross-entropy over every label."""
    logits = forward(m, params, tokens, mode)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
