"""The harness takes its architecture from the module that a configuration
file names under ``"reference"``: the dense decoder reads exactly as it did
when the harness knew only it, and an architecture unlike it (the test-only
``data/toy_moe.py``) goes through weights, operation counts, the program's
``ModelConfig`` and the check with no harness file that names it."""
import gzip
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import check, flops, serve, spec, weights

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
DANUBE = json.loads((REPO / "bench/configs/danube3.json").read_text())
YI = json.loads((REPO / "bench/configs/yi9b.json").read_text())
MOE = json.loads((DATA / "toy_moe.json").read_text())


# -- the dense decoder reads as before ---------------------------------------
# Digest and counts computed by the harness as it was before architectures
# were read from the configuration's module, on the CPU.
DIGEST = "5bfaeb425e0b3e9beecf9f2e69de905d58c57345770fb5495826a4f8e7b0c0f1"
FORWARD = {1: 7677911040.0, 257: 7772282880.0, 1800: 8341094400.0,
           4096: 9187491840.0, 5000: 9187491840.0}
TRAIN = {1: 23033733120.0, 64: 23068569600.0, 4096: 25298104320.0,
         8192: 26430289920.0}
LEAST = [([100, 300], 4.591120879120879e-05),
         ([64, 256, 1800, 2000] * 8, 0.003723309010989011),
         ([5000, 70], 0.0004696896703296704)]


def _digest(params) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        a = np.asarray(leaf)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_dense_weights_same_bits():
    small = dict(DANUBE, n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                 head_dim=16, d_ff=96, vocab_size=256)
    assert _digest(weights.make(small, jax.random.key(20261020))) == DIGEST


def _twelve_keys(m):
    """``model_config`` as it was: twelve keys, the family fixed."""
    from repro.configs.base import ModelConfig

    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
            "vocab_size", "head_dim", "sliding_window", "rope_theta",
            "norm_eps", "act", "tie_embeddings")
    return ModelConfig(name=m["name"], family="dense",
                       dtype=getattr(jnp, m["dtype"]),
                       **{k: m[k] for k in keys if k in m})


@pytest.mark.parametrize("m", [DANUBE, YI], ids=["danube3", "yi9b"])
def test_dense_model_config_unchanged(m):
    assert serve.model_config(m) == _twelve_keys(m)


def test_dense_counts_unchanged():
    for ctx, want in FORWARD.items():
        assert flops.forward_token_flops(DANUBE, ctx) == want
    for seq, want in TRAIN.items():
        assert flops.train_token_flops(DANUBE, seq) == want
    for ctx, want in LEAST:
        assert flops.paged_decode_attention_least_time(
            DANUBE, ctx, 197e12, 819e9) == want


def test_dense_roofline_of_a_recorded_window():
    """Every decode step of a window recorded on the chip: the least time is
    the one the roofline reader computed as one layer's cost times the
    layers."""
    with gzip.open(REPO / "bench/data/danube3.decode_heavy.trace.json.gz",
                   "rt") as f:
        steps = [s for s in json.load(f)["steps"] if s["decoded"] and s["ctx"]]
    assert steps
    for s in steps:
        one_layer = flops.paged_decode_attention_cost(DANUBE, s["ctx"])
        assert flops.paged_decode_attention_least_time(
            DANUBE, s["ctx"], 197e12, 819e9) == DANUBE["n_layers"] * \
            flops.least_time(*one_layer, 197e12, 819e9)


# -- an architecture the harness does not name -------------------------------
@pytest.fixture(scope="module")
def moe():
    return MOE, weights.make(MOE, jax.random.key(5))


def test_moe_weights_follow_its_tree(moe):
    m, params = moe
    L, d, E, f = m["n_layers"], m["d_model"], m["n_experts"], m["d_ff"]
    e = params["layers"]["moe"]
    assert e["router"].shape == (L, d, E) and e["router"].dtype == jnp.float32
    assert e["w_gate"].shape == (L, E, d, f) and e["w_down"].shape == (L, E, f, d)
    others = [x for x in jax.tree.leaves(params) if x is not e["router"]]
    assert {x.dtype for x in others} == {jnp.dtype(jnp.bfloat16)}
    # norm gains at 0.1, matrices at fan-in
    assert float(jnp.std(params["layers"]["ln1"].astype(jnp.float32))) == \
        pytest.approx(0.1, rel=0.2)
    assert float(jnp.std(e["router"])) == pytest.approx(d ** -0.5, rel=0.1)


def test_moe_counts_come_from_its_module():
    d, hd, H, Hkv, V = 64, 16, 4, 2, 512
    per_layer = d * hd * (H + 2 * Hkv) + H * hd * d + d * 8 + 2 * 3 * d * 32
    assert flops.matmul_params(MOE) == 4 * per_layer + d * V
    # three layers see at most 6 positions, the full layer all of them
    ctx = 20
    want = 2.0 * flops.matmul_params(MOE) + 4 * H * hd * (3 * 6 + ctx)
    assert flops.forward_token_flops(MOE, ctx) == want
    f, b = flops.paged_decode_attention_cost(dict(MOE, sliding_window=None), [ctx])
    fw, bw = flops.paged_decode_attention_cost(MOE, [ctx])
    assert flops.paged_decode_attention_least_time(MOE, [ctx], 1.0, 1.0) == \
        3 * max(fw, bw) + max(f, b)


def test_moe_model_config():
    cfg = serve.model_config(MOE)
    assert (cfg.family, cfg.n_experts, cfg.top_k) == ("moe", 8, 2)
    assert cfg.sliding_window == 6 and cfg.dtype == jnp.bfloat16


def _greedy(m, params, prompt, n):
    """``n`` tokens, each the module's own argmax after the ones before; one
    padded length (causal attention keeps the padding out)."""
    arch = spec.architecture(m)
    fwd = jax.jit(lambda p, t: arch.forward(m, p, t))
    toks = np.zeros((1, len(prompt) + n), np.int32)
    toks[0, :len(prompt)] = prompt
    for j in range(len(prompt), len(prompt) + n):
        toks[0, j] = int(jnp.argmax(fwd(params, toks)[0, j - 1]))
    return toks[0, len(prompt):]


def test_moe_check_reads_its_forward(moe):
    m, params = moe
    prompt = np.asarray(jax.random.randint(jax.random.key(9), (14,), 1,
                                           m["vocab_size"]), np.int32)
    served = _greedy(m, params, prompt, 10)
    assert check.widest_gap(m, params, [(prompt, served)], 32) == 0.0
    altered = served.copy()
    altered[4] = (altered[4] + 1) % m["vocab_size"]
    assert check.widest_gap(m, params, [(prompt, altered)], 32) > 0.0


def test_moe_window_binds(moe):
    """The per-layer windows change the logits, so the module reads them."""
    m, params = moe
    arch = spec.architecture(m)
    toks = jax.random.randint(jax.random.key(3), (1, 16), 0, m["vocab_size"])
    full = dict(m, layer_types=["full_attention"] * m["n_layers"])
    a = arch.forward(m, params, toks)
    b = arch.forward(full, params, toks)
    assert np.array_equal(a[0, :6], b[0, :6])
    assert not np.allclose(a[0, 6:], b[0, 6:])


@pytest.mark.parametrize("use", ["model_config", "weights", "flops"])
def test_file_without_reference_fails(use):
    m = {k: v for k, v in MOE.items() if k != "reference"}
    call = {"model_config": lambda: serve.model_config(m),
            "weights": lambda: weights.make(m, jax.random.key(0)),
            "flops": lambda: flops.forward_token_flops(m, 8)}[use]
    with pytest.raises(KeyError, match="reference"):
        call()


@pytest.mark.parametrize("m", [MOE, DANUBE], ids=["toy_moe", "danube3"])
def test_unknown_key_fails(m):
    with pytest.raises(KeyError, match="d_mdoel"):
        serve.model_config(dict(m, d_mdoel=4096))
