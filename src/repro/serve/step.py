"""Serving step functions (the ``serve_step`` the decode/long shapes lower)
and the shared next-token sampling used by both engines.

``decode`` shapes lower ONE new token against a KV cache of ``seq_len`` —
the memory-bandwidth-bound regime; caches are sequence-sharded over the
model axis (dist/sharding.cache_pspecs) so MQA archs scale too.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer

__all__ = [
    "make_prefill_step",
    "make_decode_step",
    "make_paged_decode_step",
    "make_prefill_chunk_step",
    "mask_pad_vocab",
    "sample_tokens",
]


def make_prefill_step(cfg: ModelConfig) -> Callable:
    def prefill_step(params, cache, batch):
        return transformer.prefill(cfg, params, batch, cache)

    return prefill_step


def make_decode_step(cfg: ModelConfig) -> Callable:
    def decode_step(params, cache, tokens):
        logits, cache = transformer.decode_step(cfg, params, tokens, cache)
        return logits, cache

    return decode_step


def make_paged_decode_step(cfg: ModelConfig) -> Callable:
    """Batched decode over the block-paged KV cache: each batch row reads
    physical pages through its page-table row (``cache["table"]``) and the
    step returns its new K/V, ``[L, B, Hkv, hd]``, in place of a cache — the
    engine installs them (``transformer.paged_install_decode``), so this
    graph is read-only over the pool."""

    def paged_decode_step(params, cache, tokens):
        return transformer.paged_decode_step(cfg, params, tokens, cache)

    return paged_decode_step


def make_prefill_chunk_step(cfg: ModelConfig, page_size: int) -> Callable:
    """One page-aligned prompt chunk of a single request: reads context K/V
    from the pool (strictly below ``start``), returns the chunk's K/V
    *without writing* — the engine scatters it in afterwards, so this graph
    and the decode step read the same pool concurrently."""

    def prefill_chunk_step(params, pages, table_row, batch, start, valid_len):
        return transformer.paged_prefill_chunk(
            cfg, params, batch["tokens"], pages, table_row, start, valid_len,
            page_size=page_size)

    return prefill_chunk_step


def mask_pad_vocab(logits: jax.Array, vocab_size: int) -> jax.Array:
    """-inf the padded-vocab tail of ``logits[..., vocab_size:]``.

    The model's unembedding spans ``cfg.padded_vocab`` columns (Megatron
    sharding padding) and the ``vocab_size..padded_vocab`` region carries
    *random initialized weight* — without this mask both greedy argmax and
    temperature sampling can emit token ids that do not exist.
    """
    if logits.shape[-1] <= vocab_size:
        return logits
    mask = jnp.arange(logits.shape[-1]) >= vocab_size
    return jnp.where(mask, -jnp.inf, logits)


def sample_tokens(
    logits: jax.Array,
    vocab_size: int,
    temperature: float,
    key: jax.Array | None = None,
) -> jax.Array:
    """Next-token ids from last-position logits ``[..., padded_vocab]``.

    Greedy argmax at ``temperature == 0``, else categorical — both over the
    pad-masked vocabulary, so every emitted id is ``< vocab_size``.
    """
    logits = mask_pad_vocab(logits, vocab_size)
    if temperature > 0:
        if key is None:
            raise ValueError("temperature sampling needs a PRNG key")
        return jax.random.categorical(key, logits / temperature, axis=-1)
    return jnp.argmax(logits, axis=-1)
