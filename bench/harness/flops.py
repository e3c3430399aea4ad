"""Operations and bytes that the work needs, as functions of the work and
the model's published sizes -- never of how the program computes them.

``m`` is a configuration file's contents (``bench/configs/*.json``).  What
depends on the architecture comes from the module it names
(``spec.architecture``): the weights one token multiplies through
(``matmul_params``) and each attention layer's window
(``attention_windows``).  Attention is counted here, per layer, from the
query heads, the head size and the positions a query attends to; layers
with the same window are counted together.  The embedding lookup does no
arithmetic and is not counted.  A multiply-add counts as two operations.
"""
from __future__ import annotations

from collections import Counter

from .spec import architecture

BF16 = 2   # bytes


def head_dim(m: dict) -> int:
    return int(m.get("head_dim") or m["d_model"] // m["n_heads"])


def matmul_params(m: dict) -> int:
    """Weights one token multiplies through: every layer and the head."""
    return architecture(m).matmul_params(m)


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies through in the mean layer: the whole
    count less the output head, over the layers."""
    return (matmul_params(m) - m["d_model"] * m["vocab_size"]) // m["n_layers"]


def window_groups(m: dict) -> list[tuple[dict, int]]:
    """The attention layers grouped by window: for each window, ``m`` with
    that ``sliding_window`` and the number of layers that have it."""
    windows = Counter(architecture(m).attention_windows(m))
    return [(dict(m, sliding_window=w), n) for w, n in windows.items()]


def attended(m: dict, ctx: int) -> int:
    """Positions a query at context length ``ctx`` attends to in a layer
    whose window is ``m["sliding_window"]``."""
    w = m.get("sliding_window")
    return min(ctx, w) if w else ctx


def forward_token_flops(m: dict, ctx: int) -> float:
    """One token's forward pass with ``ctx`` positions in its context (its
    own included): the matmuls, then q.k and p.v in every attention layer."""
    attn = sum(4 * n * g["n_heads"] * head_dim(g) * attended(g, ctx)
               for g, n in window_groups(m))
    return 2.0 * matmul_params(m) + attn


def train_token_flops(m: dict, seq_len: int) -> float:
    """Forward and backward operations per token of a causal sequence of
    ``seq_len`` (backward is twice the forward); recomputation excluded."""
    attn = sum(4 * n * g["n_heads"] * head_dim(g)
               * (sum(attended(g, p + 1) for p in range(seq_len)) / seq_len)
               for g, n in window_groups(m))
    return 3.0 * (2.0 * matmul_params(m) + attn)


def paged_decode_attention_cost(m: dict, ctx_lens) -> tuple[float, float]:
    """(operations, bytes) of one call of single-token attention (one layer,
    window ``m["sliding_window"]``) over rows whose contexts hold
    ``ctx_lens`` positions: each row reads its attended keys and values
    once, and its query and output once."""
    hd, hkv, hq = head_dim(m), m["n_kv_heads"], m["n_heads"]
    pos = sum(attended(m, c) for c in ctx_lens)
    flops = 4.0 * hq * hd * pos
    nbytes = pos * hkv * hd * 2 * BF16 + 2 * len(ctx_lens) * hq * hd * BF16
    return flops, float(nbytes)


def paged_decode_attention_least_time(m: dict, ctx_lens, peak_flops: float,
                                      peak_bw: float) -> float:
    """The least time of one decode step's attention, every layer's call,
    over rows whose contexts hold ``ctx_lens`` positions."""
    return sum(n * least_time(*paged_decode_attention_cost(g, ctx_lens),
                              peak_flops, peak_bw)
               for g, n in window_groups(m))


def least_time(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float) -> float:
    """The roofline: the least time the chip could take for the work."""
    return max(flops / peak_flops, nbytes / peak_bw)
