"""Mellum2 at a toy size of the same shape, through the program's serving
path and against ``bench/reference/mellum2.py``: 8 layers (two periods of
three sliding layers and a full one), window 8, YaRN over 64 original
positions (so its ramp falls inside the 16-wide head), 8 experts held of 32
routed, top 4.

The program runs in float32 here, so that the comparison with the float32
reference is tight: what differs is only the order of the sums and the
rope tables' arithmetic (float32 in the program, float64 rounded to
float32 in the reference)."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import check, flops, serve, spec, weights
from repro.models.layers import yarn_scale

REPO = Path(__file__).resolve().parents[2]
MELLUM = json.loads((REPO / "bench/configs/mellum2.json").read_text())
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# float32 on both sides; logits are O(1): the sums' order and the rope
# tables' rounding move them by about 1e-6
TOL = dict(rtol=1e-4, atol=1e-4)


def toy(**over) -> dict:
    rope = json.loads(json.dumps(MELLUM["rope_parameters"]))
    rope["full_attention"]["original_max_position_embeddings"] = 64
    m = dict(MELLUM, n_layers=8, d_model=64, n_heads=4, n_kv_heads=2,
             head_dim=16, d_ff=32, vocab_size=512, sliding_window=8,
             layer_types=PERIOD * 2, rope_parameters=rope,
             yarn_original_max_position=64, n_experts=8, router_experts=32,
             num_experts=32, top_k=4, dtype="float32")
    m.update(over)
    return m


M = toy()
ARCH = spec.architecture(M)


@pytest.fixture(scope="module")
def params():
    return weights.make(M, jax.random.key(20261022))


def test_file_keeps_the_published_config():
    """Every number of the published config.json is in the file as it is
    published, the sizes the program reads under ModelConfig's names agree
    with it, and only the experts held are cut."""
    assert MELLUM["reduced"] == ["n_experts"]
    assert MELLUM["published"] == {"n_experts": 64}
    cfg = serve.model_config(MELLUM)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.resolved_head_dim, cfg.d_ff, cfg.vocab_size) == (
        MELLUM["num_hidden_layers"], MELLUM["hidden_size"],
        MELLUM["num_attention_heads"], MELLUM["num_key_value_heads"],
        MELLUM["head_dim"], MELLUM["moe_intermediate_size"],
        MELLUM["vocab_size"])
    assert (cfg.routed_experts, cfg.n_experts, cfg.top_k, cfg.norm_eps) == (
        MELLUM["num_experts"], 16, MELLUM["num_experts_per_tok"],
        MELLUM["rms_norm_eps"])
    assert cfg.layer_types == tuple(MELLUM["layer_types"])
    yarn = MELLUM["rope_parameters"]["full_attention"]
    plain = MELLUM["rope_parameters"]["sliding_attention"]
    assert yarn["rope_type"] == "yarn" and plain["rope_type"] == "default"
    assert cfg.rope_theta == yarn["rope_theta"] == plain["rope_theta"]
    assert (cfg.yarn_factor, cfg.yarn_original_max_position) == (
        yarn["factor"], yarn["original_max_position_embeddings"])
    # the rest of the published YaRN is the program's fixed form
    assert (yarn["beta_fast"], yarn["beta_slow"]) == (32, 1)
    assert yarn_scale(cfg.yarn_factor) == yarn["attention_factor"]
    assert cfg.layer_yarn() == tuple(t == "full_attention"
                                     for t in MELLUM["layer_types"])
    assert cfg.layer_windows() == tuple(
        w or 0 for w in ARCH.attention_windows(MELLUM))
    # 16 of 64 held, top 8: two experts per token, and the 64-wide router
    d, f, hd = 2304, 896, 128
    attn = d * hd * (32 + 2 * 4) + 32 * hd * d
    assert flops.matmul_params(MELLUM) == \
        28 * (attn + d * 64 + 2 * 3 * d * f) + d * 98304


def test_weights_follow_the_program_tree(params):
    cfg = serve.model_config(M)
    from repro.models import transformer

    want = jax.eval_shape(lambda: transformer.init_params(
        cfg, jax.random.key(0)))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    assert got == jax.tree.map(lambda a: (a.shape, a.dtype), want)
    assert params["layers"]["mlp"]["router"].shape == (8, 64, 32)


def _served_logits(m, params, toks, n_prefill=4):
    """The program's serving paths without pages: ``prefill`` over the first
    ``n_prefill`` tokens, then ``decode_step`` through the rest (every
    layer's window and rope inside the one layer scan, the dropless expert
    layer); the logits at every position from ``n_prefill - 1`` on."""
    from functools import partial

    from repro.models import transformer

    cfg = serve.model_config(m)
    S = toks.shape[1]
    lg, cache = transformer.prefill(cfg, params, {"tokens": toks[:, :n_prefill]},
                                    transformer.init_cache(cfg, 1, S))
    outs = [lg[0]]
    step = jax.jit(partial(transformer.decode_step, cfg))
    for t in range(n_prefill, S):
        lg, cache = step(params, toks[:, t:t + 1], cache)
        outs.append(lg[0])
    return jnp.stack(outs)[:, :m["vocab_size"]]


def _variant(name):
    if name == "base":
        return M
    if name == "no_window":
        return toy(layer_types=["full_attention"] * 8)
    if name == "no_yarn":
        rope = json.loads(json.dumps(M["rope_parameters"]))
        rope["full_attention"] = dict(rope["sliding_attention"])
        return toy(rope_parameters=rope, yarn_factor=0.0)
    return toy(first_expert=8)           # experts 8..15 held


@pytest.mark.parametrize("name", ["base", "no_window", "no_yarn", "experts_8_15"])
def test_window_yarn_and_held_experts_bind(params, name):
    """The program agrees with the reference in each variant, and each of
    the window, YaRN and the held experts' index changes the logits of
    both: each is read, by the program and by the reference."""
    toks = jax.random.randint(jax.random.key(4), (1, 20), 1, M["vocab_size"])
    m = _variant(name)
    ref = ARCH.forward(m, params, toks)[0]
    prog = _served_logits(m, params, toks)
    np.testing.assert_allclose(prog, ref[3:], **TOL)
    if name != "base":
        base = ARCH.forward(M, params, toks)[0]
        assert float(jnp.abs(ref - base).max()) > 1e-2


def test_expert_layer_is_dropless():
    """Rows that all pick the same experts, far more of them than capacity
    routing would keep, give each row what it gives alone."""
    from repro.models.moe import expert_layer, moe_ffn

    cfg = serve.model_config(toy(router_experts=0, num_experts=8))
    e = jax.tree.map(lambda a: a[0], weights.make(toy(router_experts=0,
                     num_experts=8), jax.random.key(7))["layers"]["mlp"])
    k1, k2 = jax.random.split(jax.random.key(8))
    same = jnp.broadcast_to(jax.random.normal(k1, (1, 1, 64)), (28, 1, 64))
    x = jnp.concatenate([same, jax.random.normal(k2, (8, 1, 64))])
    batch, _ = expert_layer(e, x, top_k=cfg.top_k)
    alone = jnp.concatenate([expert_layer(e, x[i:i + 1], top_k=cfg.top_k)[0]
                             for i in range(x.shape[0])])
    np.testing.assert_allclose(batch, alone, rtol=1e-5, atol=1e-6)
    # the capacity path drops the same rows' overflow
    dropped, _ = moe_ffn(e, x.reshape(1, -1, 64), top_k=cfg.top_k)
    assert not np.allclose(dropped.reshape(x.shape), alone, atol=1e-3)


def test_four_shares_add_up_to_the_uncut_layer():
    """The expert layer of each of four chips (8 of 32 experts each), in
    the program and in the reference, sums to the reference's uncut layer
    (all 32 held)."""
    from repro.models.moe import expert_layer

    whole = toy(n_experts=32)
    e = jax.tree.map(lambda a: a[0],
                     weights.make(whole, jax.random.key(11))["layers"]["mlp"])
    h = jax.random.normal(jax.random.key(12), (2, 9, 64))
    uncut = ARCH.experts(whole, e, h)
    prog, ref = 0.0, 0.0
    for c in range(4):
        share = dict(e, **{k: e[k][8 * c:8 * c + 8]
                           for k in ("w_gate", "w_up", "w_down")})
        prog = prog + expert_layer(share, h, top_k=4, first_expert=8 * c)[0]
        ref = ref + ARCH.experts(toy(first_expert=8 * c), share, h)
    np.testing.assert_allclose(ref, uncut, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(prog, uncut, **TOL)


# -- the paged engine, as the harness builds it ------------------------------
def _routing(params, seq):
    """Per layer, the reference router's expert indices at each position."""
    toks = jnp.asarray(seq)[None]
    pos = jnp.arange(toks.shape[1])
    x = ARCH.embed(params, toks)
    out = []
    for i in range(M["n_layers"]):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        x = ARCH.attend(M, lp, i, x, pos)
        h = ARCH.rms_norm(x, lp["ln2"], M["norm_eps"])
        out.append(np.asarray(ARCH.route(M, lp["mlp"], h)[1][0]))
        x = x + ARCH.experts(M, lp["mlp"], h)
    return np.stack(out)                  # [L, S, top_k]


@pytest.fixture(scope="module", params=[False, True], ids=["jnp", "pallas"])
def served(request, params):
    """Three requests through ``PagedEngine`` (chunked prefill of 8-token
    chunks, then decode, two slots): every call's logits with the request
    and positions it serves."""
    from repro.serve.engine import Request, ServeConfig
    from repro.serve.paged import PagedConfig, PagedEngine

    mp = pytest.MonkeyPatch()
    if request.param:                     # the kernels, in interpret mode
        mp.setenv("REPRO_USE_PALLAS", "1")
    cfg = serve.model_config(M)
    eng = PagedEngine(cfg, params, ServeConfig(max_batch=2, max_len=48),
                      paged=PagedConfig(page_size=4, prefill_chunk=8))
    calls = []
    run_exe, run_chunk = eng._run_exe, eng._run_chunk

    def traced_exe(exe, args, **kw):
        out = run_exe(exe, args, **kw)
        if exe is eng._decode_exe:
            ln = np.asarray(args[1]["len"])
            rows = [(i, r.request_id, int(ln[i]))
                    for i, r in enumerate(eng.slots) if r is not None]
            calls.append(("decode", rows, np.asarray(out[0])))
        return out

    def traced_chunk(pages_in, slot, start, valid, toks, pool):
        out = run_chunk(pages_in, slot, start, valid, toks, pool)
        rid = eng.prefills[slot].req.request_id
        calls.append(("chunk", [(0, rid, start + valid - 1)], np.asarray(out[0]),
                      list(range(start, start + valid))))
        return out

    eng._run_exe, eng._run_chunk = traced_exe, traced_chunk
    rng = np.random.default_rng(5)
    prompts = {i: rng.integers(1, M["vocab_size"], n).astype(np.int32)
               for i, n in enumerate((19, 27, 12))}
    for i, p in prompts.items():
        eng.submit(Request(request_id=i, prompt=p, max_new_tokens=9))
    done = {r.request_id: np.asarray(r.output, np.int32) for r in eng.run()}
    stats = eng.stats()
    eng.close()
    mp.undo()
    return prompts, done, calls, stats


def test_engine_matches_the_reference(params, served):
    """Every chunk's last logits and every decode row's logits equal the
    reference's full forward at that position; the harness's check reads
    the served tokens as the reference's own."""
    prompts, done, calls, _ = served
    ref = {}
    for i, p in prompts.items():
        seq = np.concatenate([p, done[i][:-1]])
        ref[i] = np.asarray(ARCH.forward(M, params, jnp.asarray(seq)[None])[0])
    n = 0
    for call in calls:
        for row, rid, pos in call[1]:
            np.testing.assert_allclose(call[2][row, :M["vocab_size"]],
                                       ref[rid][pos], **TOL)
            n += 1
    assert n >= 3 * 9
    gap = check.widest_gap(M, params, [(prompts[i], done[i]) for i in prompts],
                           48)
    assert gap <= 1e-4                    # summation order only


def test_expert_counters_match_the_reference_routing(params, served):
    """``stats()``'s ``n_expert_rows`` and ``n_expert_hits`` are the pairs
    on held experts and the held experts hit that the reference's routing
    gives for the same calls, summed over layers and calls."""
    prompts, done, calls, stats = served
    route = {i: _routing(params, np.concatenate([p, done[i][:-1]]))
             for i, p in prompts.items()}
    rows = hits = 0
    for call in calls:
        if call[0] == "decode":
            picks = [route[rid][:, pos] for _, rid, pos in call[1]]
        else:
            rid = call[1][0][1]
            picks = [route[rid][:, p] for p in call[3]]
        picks = np.stack(picks, axis=1)               # [L, tokens, top_k]
        held = picks < M["n_experts"]
        rows += int(held.sum())
        hits += sum(len(set(picks[i][held[i]].tolist()))
                    for i in range(M["n_layers"]))
    assert rows > 0 and hits > 0
    assert (stats["n_expert_rows"], stats["n_expert_hits"]) == (rows, hits)
