"""Operations and bytes that the work needs, as functions of the work and
the model's published sizes -- never of how the program computes them.

``m`` is a configuration file's contents (``bench/configs/*.json``): a dense
decoder with grouped-query attention, SwiGLU feed-forward and an untied
output head.  The embedding lookup does no arithmetic and is not counted.
A multiply-add counts as two operations.
"""
from __future__ import annotations

BF16 = 2   # bytes


def head_dim(m: dict) -> int:
    return int(m.get("head_dim") or m["d_model"] // m["n_heads"])


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies through in one layer."""
    d, f, hd = m["d_model"], m["d_ff"], head_dim(m)
    attn = d * hd * (m["n_heads"] + 2 * m["n_kv_heads"]) + m["n_heads"] * hd * d
    return attn + 3 * d * f


def matmul_params(m: dict) -> int:
    """Weights one token multiplies through: every layer and the head."""
    return m["n_layers"] * layer_matmul_params(m) + m["d_model"] * m["vocab_size"]


def attended(m: dict, ctx: int) -> int:
    """Positions a query at context length ``ctx`` attends to."""
    w = m.get("sliding_window")
    return min(ctx, w) if w else ctx


def forward_token_flops(m: dict, ctx: int) -> float:
    """One token's forward pass with ``ctx`` positions in its context (its
    own included): the matmuls, then q.k and p.v in every layer."""
    attn = 4 * m["n_layers"] * m["n_heads"] * head_dim(m) * attended(m, ctx)
    return 2.0 * matmul_params(m) + attn


def train_token_flops(m: dict, seq_len: int) -> float:
    """Forward and backward operations per token of a causal sequence of
    ``seq_len`` (backward is twice the forward); recomputation excluded."""
    mean_ctx = sum(attended(m, p + 1) for p in range(seq_len)) / seq_len
    attn = 4 * m["n_layers"] * m["n_heads"] * head_dim(m) * mean_ctx
    return 3.0 * (2.0 * matmul_params(m) + attn)


def paged_decode_attention_cost(m: dict, ctx_lens) -> tuple[float, float]:
    """(operations, bytes) of one call of single-token attention (one layer)
    over rows whose contexts hold ``ctx_lens`` positions: each row reads its
    attended keys and values once, and its query and output once."""
    hd, hkv, hq = head_dim(m), m["n_kv_heads"], m["n_heads"]
    pos = sum(attended(m, c) for c in ctx_lens)
    flops = 4.0 * hq * hd * pos
    nbytes = pos * hkv * hd * 2 * BF16 + 2 * len(ctx_lens) * hq * hd * BF16
    return flops, float(nbytes)


def least_time(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float) -> float:
    """The roofline: the least time the chip could take for the work."""
    return max(flops / peak_flops, nbytes / peak_bw)
