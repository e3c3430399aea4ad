"""Capture parity suite: ``compile(fn).graph.execute()`` must match ``fn``
numerically for every model family, plus node-count / flops sanity checks.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.configs.base import ModelConfig, ShapeSpec
from repro.core.capture import capture
from repro.models import api as model_api
from repro.models import transformer
from repro.train.step import compile_lm_loss, lm_loss_fn

SHAPE = ShapeSpec("cap", 16, 2, "train")

_BASE = dict(
    n_layers=2, d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
    vocab_size=128, act="silu", scan_layers=False, dtype=jnp.float32,
)

TINY = {
    "transformer": ModelConfig(name="cap-dense", family="dense", **_BASE),
    "moe": ModelConfig(name="cap-moe", family="moe", n_experts=4, top_k=2, **_BASE),
    "mamba": ModelConfig(name="cap-ssm", family="ssm", block_pattern=("ssm",),
                         ssm_state=8, **_BASE),
    "griffin": ModelConfig(name="cap-hybrid", family="hybrid",
                           block_pattern=("rglru", "rglru", "attn"),
                           lru_width=32, **{**_BASE, "n_layers": 3}),
}


def _setup(family):
    cfg = TINY[family]
    params = transformer.init_params(cfg, jax.random.key(0))
    batch = model_api.make_batch(cfg, SHAPE, jax.random.key(1))
    return cfg, params, batch


# ---------------------------------------------------------------------------
# parity: captured graph execution == uncompiled JAX, per model family
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", sorted(TINY))
def test_capture_parity_sequential(family):
    cfg, params, batch = _setup(family)
    fn = lm_loss_fn(cfg)
    exe = repro.compile(fn, params, batch)
    ref = fn(params, batch)
    got = exe.captured.run(params, batch)       # Graph.execute oracle
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert len(exe.graph) >= 20, f"{family}: graph too coarse ({len(exe.graph)})"
    assert exe.graph.total_flops() > 0
    assert exe.graph.width() >= 2


@pytest.mark.parametrize("family", ["transformer", "moe"])
def test_capture_parity_host_runtime(family):
    cfg, params, batch = _setup(family)
    fn = lm_loss_fn(cfg)
    exe = repro.compile(fn, params, batch, backend="host")
    got = exe(params, batch)
    ref = fn(params, batch)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert len({e.executor for e in exe.last_run.trace}) >= 2


# ---------------------------------------------------------------------------
# acceptance: the compile_lm_loss entry point (ISSUE 2)
# ---------------------------------------------------------------------------

def test_compile_lm_loss_entry_point():
    cfg, params, batch = _setup("transformer")
    exe = compile_lm_loss(cfg, SHAPE, backend="host")
    g = exe.graph
    assert len(g) >= 20
    assert g.width() >= 2
    # non-trivial host schedule on the real inputs
    out = exe(params, batch)
    ref = lm_loss_fn(cfg)(params, batch)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert len({e.executor for e in exe.last_run.trace}) >= 2
    assert exe.last_run.makespan > 0


def test_compile_lm_loss_grad_graph_is_larger():
    cfg = TINY["transformer"]
    fwd = compile_lm_loss(cfg, SHAPE, backend="sim")
    both = compile_lm_loss(cfg, SHAPE, backend="sim", grad=True)
    # the paper: backward roughly doubles nodes and available parallelism
    assert len(both.graph) > 1.5 * len(fwd.graph)
    assert both.graph.total_flops() > 2 * fwd.graph.total_flops()


# ---------------------------------------------------------------------------
# structural sanity of the capture itself
# ---------------------------------------------------------------------------

def test_matmul_flops_exact():
    cg = capture(lambda a, b: a @ b, jnp.ones((8, 32)), jnp.ones((32, 4)))
    gemms = [n for n in cg.graph.nodes if n.kind == "gemm"]
    assert len(gemms) == 1
    assert gemms[0].flops == 2 * 8 * 32 * 4
    assert gemms[0].meta["rows"] == 8


def test_scatter_flops_priced_by_update_size():
    # a paged-KV decode graph writes one token row into a pool thousands of
    # times larger; pricing the scatter by its output buffer would dwarf the
    # real work and skew partitioning
    pool = jnp.zeros((1024, 64))
    upd = jnp.ones((64,))
    cg = capture(lambda p, u: p.at[0].set(u), pool, upd)
    work = sum(n.flops for n in cg.graph.nodes if n.kind != "input")
    assert work < pool.size


def test_elementwise_chain_fuses_into_consumer():
    def f(x, w):
        return jnp.sum(jnp.tanh(x @ w) * 2.0 + 1.0)

    cg = capture(f, jnp.ones((4, 4)), jnp.ones((4, 4)))
    # tanh/mul/add collapse into the gemm or the reduce; only inputs +
    # gemm + reduce survive
    kinds = [n.kind for n in cg.graph.nodes]
    assert kinds.count("gemm") == 1
    assert len(cg.graph) <= 4
    assert cg.n_eqns > len([n for n in cg.graph.nodes if n.kind != "input"])


def test_shared_layer_jaxprs_get_fresh_identities():
    # two call sites of one jitted fn share a traced jaxpr; capture must
    # alpha-rename or the second call aliases the first's values
    @jax.jit
    def layer(x, w):
        return jnp.tanh(x @ w)

    def f(x, w1, w2):
        return jnp.sum(layer(layer(x, w1), w2))

    x, w1, w2 = (jnp.asarray(np.random.default_rng(i).normal(size=(8, 8)),
                             jnp.float32) for i in range(3))
    cg = capture(f, x, w1, w2)
    got, ref = cg.run(x, w1, w2), f(x, w1, w2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)
    assert len([n for n in cg.graph.nodes if n.kind == "gemm"]) == 2


def test_scan_costs_scale_with_trip_count():
    def body(c, x):
        return c @ x, c.sum()

    def f(c, xs):
        out, ys = jax.lax.scan(body, c, xs)
        return out.sum() + ys.sum()

    c = jnp.ones((4, 4))
    xs8 = jnp.ones((8, 4, 4))
    xs2 = jnp.ones((2, 4, 4))
    g8 = capture(f, c, xs8).graph
    g2 = capture(f, c, xs2).graph
    s8 = sum(n.flops for n in g8.nodes if n.kind == "scan")
    s2 = sum(n.flops for n in g2.nodes if n.kind == "scan")
    assert s8 == pytest.approx(4 * s2)
    got = capture(f, c, xs8).run(c, xs8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(f(c, xs8)), rtol=1e-6)


def test_capture_multi_output_pytree():
    def f(x):
        return {"a": x * 2, "b": (x.sum(), x - 1)}

    x = jnp.arange(6.0).reshape(2, 3)
    cg = capture(f, x)
    got, ref = cg.run(x), f(x)
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(ref)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_capture_rejects_wrong_arg_structure():
    cg = capture(lambda x: x * 2, jnp.ones((3,)))
    with pytest.raises(TypeError):
        cg.bind((jnp.ones((3,)), jnp.ones((3,))))


def test_capture_from_shape_structs_runs_on_concrete():
    spec = jax.ShapeDtypeStruct((4, 4), jnp.float32)
    cg = capture(lambda a, b: jnp.sum(a @ b), spec, spec)
    a = jnp.asarray(np.random.default_rng(0).normal(size=(4, 4)), jnp.float32)
    b = jnp.asarray(np.random.default_rng(1).normal(size=(4, 4)), jnp.float32)
    np.testing.assert_allclose(np.asarray(cg.run(a, b)),
                               np.asarray(jnp.sum(a @ b)), rtol=1e-6)


# ---------------------------------------------------------------------------
# Pallas kernel nodes: the TPU decode graph holds a pallas_call equation
# ---------------------------------------------------------------------------

def _paged_decode_case(scan_layers):
    from repro.configs.base import get_config

    cfg = get_config("h2o-danube3-4b", smoke=True).reduced(
        dtype=jnp.float32, scan_layers=scan_layers)
    params = transformer.init_params(cfg, jax.random.key(0))
    ps, n_pages = 8, 8
    cache = transformer.init_paged_cache(cfg, 2, 40, n_pages=n_pages, page_size=ps)
    rng = np.random.default_rng(0)
    cache["pages"] = jax.tree.map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype), cache["pages"])
    # row 0: 21 tokens on pages 3,0,5 (past the smoke window of 16);
    # row 1: 6 tokens on page 1
    cache["table"][0, :3] = (3, 0, 5)
    cache["table"][1, 0] = 1
    cache["len"][:] = (21, 6)
    cache = {k: jnp.asarray(v) if k != "pages" else v for k, v in cache.items()}
    tokens = jnp.asarray([[7], [11]], jnp.int32)
    return cfg, params, cache, tokens, ps


@pytest.mark.parametrize("scan_layers", [False, True], ids=["unrolled", "scanned"])
def test_paged_decode_kernel_graph_replays_bit_exact(monkeypatch, scan_layers):
    """The paged decode step on the Pallas kernel (interpret mode here)
    captures, replays through ``Graph.execute`` and a static host plan bit
    for bit, agrees with the jnp gather path, and passes the checks."""
    from repro.checks import infer_effects
    from repro.serve.step import make_paged_decode_step

    cfg, params, cache, tokens, ps = _paged_decode_case(scan_layers)
    step = make_paged_decode_step(cfg)
    jnp_logits, jnp_cache = step(params, cache, tokens)
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    direct_logits, direct_cache = step(params, cache, tokens)

    exe = repro.compile(step, params, cache, tokens, backend="host",
                        host_mode="static", n_executors=2, team_size=1)
    kernels = [n for n in exe.graph.names
               if "pallas_call" in exe.graph[n].meta.get("prims", ())]
    if scan_layers:      # the kernel sits inside the layer scan's body
        assert not kernels
    else:                # one kernel node per layer, never fused away
        assert len(kernels) == cfg.n_layers
        assert all(exe.graph[n].kind == "kernel" and exe.graph[n].flops > 0
                   for n in kernels)

    oracle = exe.captured.run(params, cache, tokens)      # Graph.execute
    planned = exe(params, cache, tokens)                  # static host plan
    for got in (oracle, planned):
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(
                (direct_logits, direct_cache))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # online softmax over pages vs one full softmax: equal up to rounding
    for a, b in zip(jax.tree.leaves((direct_logits, direct_cache)),
                    jax.tree.leaves((jnp_logits, jnp_cache))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)

    eff = infer_effects(exe.graph)
    for n in kernels:       # a kernel reads its operands and writes no input
        assert eff.effects[n].reads and not eff.effects[n].writes
    assert exe.verify(hazards=True).ok
