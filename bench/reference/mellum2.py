"""Plain reference of Mellum2-12B-A2.5B (JetBrains, config.json of
Mellum2-12B-A2.5B-Instruct), as its configuration describes it: RMSNorm,
grouped-query attention (32 query heads, 4 key/value heads of 128) with
rotary embedding on the two halves of each head, three sliding-window
layers to each full-attention layer (``layer_types``), YaRN rope on the
full layers and plain rope on the windowed ones (``rope_parameters``), and
in every layer a softmax router over all ``num_experts`` experts whose top
``top_k`` are renormalised (``norm_topk_prob``), each expert a SwiGLU; an
untied output head.  Straight ``jax.numpy`` in float32 at
``Precision.HIGHEST``; nothing of the program under test is imported.

It computes one chip's share of an expert-parallel deployment: the experts
``first_expert .. first_expert + n_experts - 1`` of every layer (the
weights' leading axis), each computed for every token and weighted by the
token's gate, 0 where the router did not pick it.  The experts not held add
nothing, here as in the program: their share is the other chips'.

Departures from the published model, each also in the configuration's
``assumed``: the rotary embedding rotates the two halves of each head, as
Llama and Qwen2 do; the router takes the softmax before the top-k; no norm
on q or k (the configuration declares none); a window holds the last
``sliding_window`` positions, the query's own included; the multi-token
prediction head that the model card mentions is left out, since the
configuration declares none.  As in ``dense_gqa.py``, a norm's gain is
stored as ``scale`` and applied as ``1 + scale``.

Besides the forward pass it gives the benchmark the weight tree in the
program's layout (``shapes``; the router in float32), the weights a token
multiplies through (``matmul_params``) and each layer's window
(``attention_windows``).  ``mode="fp8"`` is the control of
``dense_gqa.py``: every product with its operands rounded to float8.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense_gqa import embed, head, head_dim, mm, rms_norm

# the published configuration's keys that the program's ModelConfig does not
# have; this module reads those it needs
OWN_KEYS = (
    "attention_bias", "hidden_act", "hidden_size", "intermediate_size",
    "max_position_embeddings", "max_window_layers", "mlp_layer_types",
    "model_type", "moe_intermediate_size", "norm_topk_prob",
    "num_attention_heads", "num_experts", "num_experts_per_tok",
    "num_hidden_layers", "num_key_value_heads", "rms_norm_eps",
    "rope_parameters", "tie_word_embeddings", "use_sliding_window",
)


def shapes(m: dict) -> dict:
    """The weight tree: each leaf's shape, layers stacked on a leading axis;
    the router over all ``num_experts``, the experts those held."""
    L, d, f, V = m["n_layers"], m["d_model"], m["d_ff"], m["vocab_size"]
    hd, H, Hkv = head_dim(m), m["n_heads"], m["n_kv_heads"]
    E, R = m["n_experts"], m["num_experts"]
    return {
        "embed": (V, d), "final_norm": (d,), "unembed": (d, V),
        "layers": {
            "ln1": (L, d), "ln2": (L, d),
            "attn": {"wq": (L, d, H * hd), "wk": (L, d, Hkv * hd),
                     "wv": (L, d, Hkv * hd), "wo": (L, H * hd, d)},
            "mlp": {"router": jax.ShapeDtypeStruct((L, d, R), jnp.float32),
                    "w_gate": (L, E, d, f), "w_up": (L, E, d, f),
                    "w_down": (L, E, f, d)},
        },
    }


def matmul_params(m: dict) -> int:
    """Weights one token multiplies through: q, k, v and o, the router over
    all experts, and the experts it can expect among those held (``top_k``
    times the held share: 2 of 8 when 16 of 64 are held) in every layer;
    and the head."""
    d, f, hd, H = m["d_model"], m["d_ff"], head_dim(m), m["n_heads"]
    attn = d * hd * (H + 2 * m["n_kv_heads"]) + H * hd * d
    experts = m["top_k"] * m["n_experts"] / m["num_experts"]
    moe = d * m["num_experts"] + experts * 3 * d * f
    return int(m["n_layers"] * (attn + moe) + d * m["vocab_size"])


def attention_windows(m: dict) -> list:
    """Each layer's window: ``sliding_window`` for a sliding layer, None
    (the whole context) for a full one."""
    return [m["sliding_window"] if t == "sliding_attention" else None
            for t in m["layer_types"]]


def _inv_freq(hd: int, p: dict) -> tuple[np.ndarray, float]:
    """Inverse frequencies [hd/2] and the cos/sin scale of one layer type's
    ``rope_parameters`` (HF ``_compute_default_rope_parameters`` and
    ``_compute_yarn_parameters`` with ``truncate``), in float64."""
    base = float(p["rope_theta"])
    inv = 1.0 / base ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    if p.get("rope_type", "default") == "default":
        return inv, 1.0
    if p["rope_type"] != "yarn":
        raise ValueError(f"rope_type {p['rope_type']!r}")
    factor, orig = float(p["factor"]), float(p["original_max_position_embeddings"])

    def dim(rot):
        return hd * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(dim(p.get("beta_fast", 32))), 0)
    high = min(math.ceil(dim(p.get("beta_slow", 1))), hd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(hd // 2) - low) / (high - low), 0.0, 1.0)
    inv = inv / factor * ramp + inv * (1.0 - ramp)
    scale = p.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return inv, float(scale)


def rope_tables(m: dict):
    """Per layer: inverse frequencies [L, hd/2] and cos/sin scale [L],
    float32."""
    tables = [_inv_freq(head_dim(m), m["rope_parameters"][t])
              for t in m["layer_types"]]
    return (jnp.asarray(np.stack([t[0] for t in tables]), jnp.float32),
            jnp.asarray([t[1] for t in tables], jnp.float32))


def rope(x, pos, inv, scale):
    """x: [..., S, H, hd]; pos: [S]; inv: [hd/2]."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, pos, window, mode: str):
    """Causal softmax attention, one key/value head (and its query heads) at
    a time; ``window`` may be traced, 0 for the whole context."""
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, S, Hkv, G, hd).transpose(2, 0, 1, 3, 4)
    keep = pos[None, :] <= pos[:, None]
    keep &= (window <= 0) | (pos[None, :] > pos[:, None] - window)

    def one(args):
        qh, kh, vh = args                 # [B, S, G, hd], [B, S, hd] x 2
        s = mm("bqgd,bkd->bgqk", qh, kh, mode) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return mm("bgqk,bkd->bqgd", p, vh, mode)

    o = jax.lax.map(one, (qg, k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)))
    return o.transpose(1, 2, 0, 3, 4).reshape(B, S, H * hd)


def attend(m: dict, lp: dict, i, x, pos, mode: str = "f32"):
    """Layer ``i``'s attention with its residual (``i`` may be traced)."""
    B, S, _ = x.shape
    H, Hkv, hd = m["n_heads"], m["n_kv_heads"], head_dim(m)
    window = jnp.asarray([w or 0 for w in attention_windows(m)])[i]
    inv, scale = rope_tables(m)
    a = lp["attn"]
    h = rms_norm(x, lp["ln1"], m["norm_eps"])
    q = mm("bsd,de->bse", h, a["wq"], mode).reshape(B, S, H, hd)
    k = mm("bsd,de->bse", h, a["wk"], mode).reshape(B, S, Hkv, hd)
    v = mm("bsd,de->bse", h, a["wv"], mode).reshape(B, S, Hkv, hd)
    q, k = rope(q, pos, inv[i], scale[i]), rope(k, pos, inv[i], scale[i])
    return x + mm("bse,ed->bsd", _attention(q, k, v, pos, window, mode),
                  a["wo"], mode)


def route(m: dict, e: dict, h, mode: str = "f32"):
    """The router's choice for each token: the top ``top_k`` of the softmax
    over all ``num_experts`` (their weights, renormalised over them when
    ``norm_topk_prob``), and their indices."""
    probs = jax.nn.softmax(mm("bsd,de->bse", h, e["router"], mode), axis=-1)
    top, idx = jax.lax.top_k(probs, m["top_k"])
    if m["norm_topk_prob"]:
        top = top / top.sum(axis=-1, keepdims=True)
    return top, idx


def experts(m: dict, e: dict, h, mode: str = "f32"):
    """The held experts' part of the expert layer: every held expert over
    every token, weighted by the token's gate for it (0 unless routed)."""
    top, idx = route(m, e, h, mode)
    gates = jnp.sum(jax.nn.one_hot(idx - m["first_expert"], m["n_experts"])
                    * top[..., None], axis=-2)
    g = jax.nn.silu(mm("bsd,edf->bsef", h, e["w_gate"], mode))
    u = mm("bsd,edf->bsef", h, e["w_up"], mode)
    y = mm("bsef,efd->bsed", g * u, e["w_down"], mode)
    return jnp.einsum("bsed,bse->bsd", y, gates, precision=jax.lax.Precision.HIGHEST)


def block(m: dict, lp: dict, i, x, pos, mode: str = "f32"):
    """Decoder layer ``i`` (weights ``lp``; ``i`` may be traced).
    x: [B, S, d] float32."""
    x = attend(m, lp, i, x, pos, mode)
    return x + experts(m, lp["mlp"], rms_norm(x, lp["ln2"], m["norm_eps"]),
                       mode)


def forward(m: dict, params, tokens, mode: str = "f32"):
    """Logits [B, S, V] of token ids [B, S] (whole model at once: for small
    sizes)."""
    pos = jnp.arange(tokens.shape[1])
    x = embed(params, tokens)
    for i in range(m["n_layers"]):
        x = block(m, jax.tree.map(lambda a: a[i], params["layers"]), i, x,
                  pos, mode)
    return head(m, params, x, mode)
