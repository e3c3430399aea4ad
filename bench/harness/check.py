"""The comparison that decides ``correct``, against the plain reference.

The reference is the forward pass of the module that the configuration
names under ``"reference"`` (``spec.architecture``).  Serving: for each
sampled request the reference runs once over its prompt and the tokens the
engine served, and each served token's logit is read against the
reference's best at its position.  The number compared is the widest such
gap over the sample (0 where every served token is the reference's first
choice).  The reference runs layer by layer, one request at a time, after
the engine and its cache are gone, so it fits beside the weights.
"""
from __future__ import annotations

import numpy as np

from .spec import architecture


def _bucket(n: int, step: int = 128) -> int:
    return -(-n // step) * step


class Reference:
    """The reference's logits over one request's prompt and served tokens,
    one jitted layer reused across layers and requests.  Every sequence is
    padded to ``length`` (the cell's longest), so that one compiled program
    serves every run of the cell; causal attention keeps the padding out."""

    def __init__(self, m: dict, params, length: int, mode: str = "f32"):
        import jax
        import jax.numpy as jnp

        self.m, self.params, self.length = m, params, _bucket(length)
        ref = architecture(m)

        def layer(layers, i, x, pos):
            lp = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
                layers)
            return ref.block(m, lp, i, x, pos, mode)

        self._layer = jax.jit(layer)
        self._embed = jax.jit(ref.embed)
        self._head = jax.jit(lambda p, x: ref.head(m, p, x, mode))
        self.jnp = jnp

    def inputs(self, prompt: np.ndarray, served: np.ndarray):
        """Token ids [1, S], and per position the served token it produced
        (-1 where none)."""
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        S = max(self.length, _bucket(len(seq)))
        toks = np.zeros((1, S), np.int32)
        toks[0, :len(seq)] = seq
        target = np.full(S, -1, np.int32)
        target[len(prompt) - 1:len(prompt) - 1 + len(served)] = served
        return toks, target

    def logits(self, toks: np.ndarray):
        """[S, V] float32 logits, on the device."""
        jnp = self.jnp
        x = self._embed(self.params, jnp.asarray(toks))
        pos = jnp.arange(toks.shape[1])
        for i in range(self.m["n_layers"]):
            x = self._layer(self.params["layers"], jnp.int32(i), x, pos)
        return self._head(self.params, x[0])


def _gaps(lg, target, chosen):
    """Per position: the best logit minus the logit of ``chosen``; 0 where
    ``target`` marks no served token."""
    import jax.numpy as jnp

    pick = jnp.take_along_axis(lg, jnp.maximum(chosen, 0)[:, None], axis=1)[:, 0]
    return jnp.where(target >= 0, lg.max(axis=-1) - pick, 0.0)


def widest_gap(m: dict, params, requests, length: int) -> float:
    """The number compared in a serving cell: the widest gap by which a
    served token's logit lies below the reference's best, over the sample."""
    import jax

    r = Reference(m, params, length)
    gaps = jax.jit(lambda lg, t: _gaps(lg, t, t).max())
    return max((float(gaps(r.logits(toks), target))
                for toks, target in (r.inputs(p, s) for p, s in requests)),
               default=float("nan"))


def control_gap(m: dict, params, requests, length: int) -> float:
    """The control's reading: at each position of the same prompts and
    served tokens, the gap (under the float32 reference) of the token that
    the float8 reference puts first."""
    import jax

    r32, r8 = Reference(m, params, length), Reference(m, params, length, "fp8")
    gaps = jax.jit(lambda lg, lg8, t: _gaps(lg, t, lg8.argmax(axis=-1)).max())
    return max((float(gaps(r32.logits(toks), r8.logits(toks), target))
                for toks, target in (r32.inputs(p, s) for p, s in requests)),
               default=float("nan"))


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and each number compared beside its limit.  A missing or
    non-finite reading fails."""
    out, ok = {}, True
    for name, value in readings.items():
        limit = limits[name]
        good = value is not None and np.isfinite(value) and value <= limit
        ok = ok and bool(good)
        out[name] = {"value": value, "limit": limit}
    return ok, out
