"""The one traffic generator: a closed loop of clients, read from a traffic
file of parameters.

The work of a run is fixed by the traffic file alone.  Request ``k`` (in
submission order) has the unique-prompt length ``prompt_lens[k % n]``, the
output length ``output_lens[k % m]`` and, where the mix shares prefixes, the
prefix ``k % prefixes.count``.  The seed draws only token ids.  No request
stops early: the engine sees no EOS id.

A closed loop starts staggered: client ``i``'s first request asks for
``ceil((i + 1) * max(output_lens) / clients)`` tokens, so completions are
spread evenly from the first step on.

The first token of every unique part differs from that of every other
request of the run.  Prefix matching then finds exactly the shared prefix
(and never an accidental one-token match of random ids), so the pages a
request maps -- and with them the engine's work -- do not depend on the seed.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np


def seed_words(seed: int, n: int) -> list[int]:
    """``n`` independent 31-bit words from any non-negative whole number."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    return [int(w) & 0x7FFFFFFF
            for w in np.random.SeedSequence(seed).generate_state(n)]


@dataclass(frozen=True)
class Planned:
    """One request as the traffic file fixes it, before token ids."""
    index: int            # submission order; negative for warm-up requests
    prefix: int | None    # which shared prefix, if any
    unique_len: int       # prompt tokens after the prefix
    output_len: int


class ClosedLoop:
    """Requests of a closed-loop serving mix, made from ``traffic`` and
    ``seed``.  ``plan(k)`` is the same for every seed; ``prompt(p)`` draws
    the ids."""

    def __init__(self, traffic: dict, seed: int, vocab_size: int):
        if traffic["kind"] != "serve_closed_loop":
            raise ValueError(f"not a closed-loop mix: {traffic['kind']!r}")
        self.t = traffic
        self.clients = int(traffic["clients"])
        self.prompt_lens = [int(x) for x in traffic["prompt_lens"]]
        self.output_lens = [int(x) for x in traffic["output_lens"]]
        pre = traffic.get("prefixes") or {}
        self.n_prefixes = int(pre.get("count", 0))
        self.prefix_len = int(pre.get("len", 0))
        self.vocab = int(vocab_size)
        w_prefix, w_ids, w_first = seed_words(seed, 3)
        self._w_ids = w_ids
        self._first0 = w_first % (self.vocab - 1)
        rng = np.random.default_rng(w_prefix)
        self.prefixes = [rng.integers(1, self.vocab, self.prefix_len,
                                      dtype=np.int64).astype(np.int32)
                         for _ in range(self.n_prefixes)]

    # -- the schedule (seed-free) --------------------------------------------
    def plan(self, k: int) -> Planned:
        """Request ``k`` of the loop (``k >= 0``)."""
        out = self.output_lens[k % len(self.output_lens)]
        if self.t.get("stagger") and k < self.clients:
            out = math.ceil((k + 1) * max(self.output_lens) / self.clients)
        return Planned(k, k % self.n_prefixes if self.n_prefixes else None,
                       self.prompt_lens[k % len(self.prompt_lens)], out)

    def warm_plans(self) -> list[Planned]:
        """Requests served before the loop starts: one per shared prefix, so
        that the prefix cache holds every prefix when the clients begin."""
        return [Planned(-1 - j, j, self.prompt_lens[0], 2)
                for j in range(self.n_prefixes)]

    # -- token ids (seeded) ---------------------------------------------------
    def prompt(self, p: Planned) -> np.ndarray:
        rng = np.random.default_rng([self._w_ids, p.index & 0xFFFFFFFF,
                                     int(p.index < 0)])
        tail = rng.integers(1, self.vocab, p.unique_len,
                            dtype=np.int64).astype(np.int32)
        tail[0] = 1 + (self._first0 + p.index) % (self.vocab - 1)
        if p.prefix is None:
            return tail
        return np.concatenate([self.prefixes[p.prefix], tail])
