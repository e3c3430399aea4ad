"""The plain reference agrees with the program's own forward pass at a toy
size in float32 (with the window binding), and the float8 control does
not."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import serve, weights
from bench.reference import dense_gqa

DATA = Path(__file__).resolve().parent / "data"



@pytest.fixture(scope="module")
def toy():
    m = dict(json.loads((DATA / "tiny.json").read_text()), sliding_window=6)
    params = weights.make(m, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, m["vocab_size"])
    return m, params, tokens


def test_reference_matches_program_forward(toy):
    from repro.models import transformer

    m, params, tokens = toy
    with jax.default_matmul_precision("highest"):
        want = transformer.forward(serve.model_config(m), params,
                                   {"tokens": tokens})[0]
    got = dense_gqa.forward(m, params, tokens)
    assert float(jnp.abs(want - got).max()) < 1e-4 * float(jnp.abs(got).max())


def test_control_differs(toy):
    m, params, tokens = toy
    a = np.asarray(dense_gqa.forward(m, params, tokens))
    b = np.asarray(dense_gqa.forward(m, params, tokens, mode="fp8"))
    assert np.abs(a - b).max() > 1e-2 * np.abs(a).max()
