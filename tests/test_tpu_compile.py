"""Ahead-of-time compiles for a described TPU v5e chip (none attached).

The TPU compiler is installed alongside jaxlib, so the Pallas kernels of the
serving path can be compiled for a chip that is only described: what the
Mosaic compiler would refuse on the chip (block shapes off the tiling, too
much VMEM) fails here instead.  The topology is described inside a fixture,
never at import: only one process may load the TPU library, and a worker
that cannot describe the chip skips these tests rather than breaking the
collection of the others.  Keep every such compile in this one file.
"""
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels.decode_attention import paged_decode_attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("arch,context", [("h2o-danube3-4b", 4096), ("gemma-2b", 2048)])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, arch, context):
    """The paged decode kernel at published head widths (h2o-danube3-4b:
    32 q / 8 kv heads of 120, sliding window 4096; gemma-2b: 8 q / 1 kv
    head of 256) lowers to a Mosaic custom call, not the jnp gather."""
    cfg = get_config(arch)
    batch, page_size = 8, 16
    n_pt = context // page_size
    hd = cfg.resolved_head_dim

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pages = spec((batch * n_pt, 2, page_size, cfg.n_kv_heads, hd), cfg.dtype)
    new_kv = spec((batch, cfg.n_kv_heads, hd), cfg.dtype)
    args = (spec((batch, 1, cfg.n_heads, hd), cfg.dtype), new_kv, new_kv,
            pages, pages, spec((), jnp.int32),
            spec((batch, n_pt), jnp.int32), spec((batch,), jnp.int32))
    fn = partial(paged_decode_attention, window=cfg.sliding_window or 0,
                 use_kernel=True, interpret=False)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# the paged engine's programs keep the page pool in place
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1}
_VIEWS = {"parameter", "get-tuple-element", "bitcast", "tuple"}
_IN_PLACE = {"scatter", "dynamic-update-slice"}


def _computations(hlo: str) -> dict[str, list[str]]:
    """Instruction lines of each computation of an HLO module's text."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            cur = line.split()[1 if line.startswith("ENTRY") else 0].lstrip("%")
            comps[cur] = []
        elif line.strip() == "}":
            cur = None
        elif cur is not None:
            comps[cur].append(line)
    return comps


_INST = re.compile(r"^\s*(ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(")


def _pool_sized_results(hlo: str, limit: int) -> list[str]:
    """Instructions that materialize a result of at least ``limit`` bytes:
    everything outside fusion bodies except parameters, views of them and
    fusions that update their operand in place."""
    comps = _computations(hlo)
    roots = {}
    for name, lines in comps.items():
        for line in lines:
            m = _INST.match(line)
            if m and m.group(1):
                roots[name] = m.group(5)
    fused = set(re.findall(r"fusion\(.*?calls=%?([\w.\-]+)", hlo))
    found = []
    for name, lines in comps.items():
        if name in fused:
            continue
        for line in lines:
            m = _INST.match(line)
            if not m:
                continue
            _, inst, dtype, dims, op = m.groups()
            size = _DTYPE_BYTES.get(dtype, 4)
            for d in filter(None, dims.split(",")):
                size *= int(d)
            if size < limit or op in _VIEWS:
                continue
            callee = re.search(r"calls=%?([\w.\-]+)", line)
            if op == "fusion" and callee and roots.get(callee.group(1)) in _IN_PLACE:
                continue
            found.append(f"{inst} = {op} {dtype}[{dims}] in {name}")
    return found


def _paged_programs(one_chip, monkeypatch, cfg, B, P, ps, T, max_len):
    """The paged engine's five programs (decode, chunk, install, insert,
    copy), compiled for the described chip; returns each one's HLO text and
    memory analysis, and the pool's and one layer's pages' bytes."""
    import repro.kernels.decode_attention.ops as ops
    import repro.kernels.moe_gmm.ops as gmm_ops
    from repro.models import transformer
    from repro.serve.step import make_paged_decode_step, make_prefill_chunk_step

    # the described chip is not the backend: steer the kernel path by hand
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    monkeypatch.setattr(gmm_ops, "default_interpret", lambda: False)
    n_pt = -(-max_len // ps)

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def on_chip(tree):
        return jax.tree.map(lambda x: spec(x.shape, x.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.key(0))))
    pages = on_chip(jax.eval_shape(lambda: transformer.init_paged_cache(
        cfg, B, max_len, n_pages=P, page_size=ps)["pages"]))
    pool_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pages))
    layer_bytes = pool_bytes // (2 * cfg.n_layers)
    hd = cfg.resolved_head_dim
    kv_step = spec((cfg.n_layers, B, cfg.n_kv_heads, hd), cfg.dtype)
    kv_chunk = spec((cfg.n_layers, T, cfg.n_kv_heads, hd), cfg.dtype)
    cache = {"len": spec((B,)), "table": spec((B, n_pt)), "pages": pages}
    programs = {
        "decode": (jax.jit(make_paged_decode_step(cfg)),
                   (params, cache, spec((B, 1)))),
        "chunk": (jax.jit(make_prefill_chunk_step(cfg, ps)),
                  (params, pages, spec((n_pt,)), {"tokens": spec((1, T))},
                   spec(()), spec(()))),
        "install": (jax.jit(partial(transformer.paged_install_decode,
                                    page_size=ps), donate_argnums=0),
                    (pages, spec((B, n_pt)), spec((B,)), kv_step, kv_step)),
        "insert": (jax.jit(partial(transformer.paged_insert_chunk,
                                   page_size=ps), donate_argnums=0),
                   (pages, spec((n_pt,)), spec(()), spec(()), kv_chunk,
                    kv_chunk)),
        "copy": (jax.jit(transformer.paged_copy_page, donate_argnums=0),
                 (pages, spec(()), spec(()))),
    }
    out = {}
    for name, (fn, args) in programs.items():
        compiled = fn.lower(*args).compile()
        out[name] = (compiled.as_text(), compiled.memory_analysis())
    return out, pool_bytes, layer_bytes


def _assert_pool_in_place(programs, pool_bytes, layer_bytes):
    for name, (hlo, mem) in programs.items():
        assert not _pool_sized_results(hlo, layer_bytes), name
        if name in ("install", "insert", "copy"):
            assert mem.alias_size_in_bytes == pool_bytes, name
    hlo, mem = programs["decode"]
    assert "tpu_custom_call" in hlo
    assert mem.temp_size_in_bytes < 64 * 2**20


@pytest.mark.parametrize("max_len", [640, 1872])
def test_paged_programs_keep_the_pool_in_place(one_chip, monkeypatch, max_len):
    """The paged engine's programs at h2o-danube3-4b's published widths and
    the benchmark cells' engine shapes (32 slots, 1 024 pages of 16,
    max_len 640 and 1 872): the decode step and the prefill chunk read the
    pool without a copy, slice or restack of a layer's pages, and each pool
    write — the decode install, a chunk insert, a copy-on-write — updates
    the donated pool in place."""
    cfg = get_config("h2o-danube3-4b")
    _assert_pool_in_place(*_paged_programs(one_chip, monkeypatch, cfg, B=32,
                                           P=1024, ps=16, T=64, max_len=max_len))


def test_mellum2_paged_programs_keep_the_pool_and_experts_in_place(
        one_chip, monkeypatch):
    """Mellum2-12B-A2.5B at its published widths with 16 of its 64 experts
    held, at ``mellum2.code_decode``'s engine shapes (32 slots, 4 672 pages
    of 16, chunks of 256, max_len 2 304): as for danube3, no pool-sized
    copy, slice or restack and every pool write in place — with 4 KV heads
    a gather over [ps, Hkv, W] rows would relayout the pool — and neither
    the decode step nor the chunk slices a layer's experts out of the
    stack: the grouped matmul, a kernel of its own (beside the paged decode
    attention in the decode step), reads them in place."""
    cfg = get_config("mellum2-12b-a2.5b").reduced(n_experts=16, router_experts=64)
    programs, pool_bytes, layer_bytes = _paged_programs(
        one_chip, monkeypatch, cfg, B=32, P=4672, ps=16, T=256, max_len=2304)
    _assert_pool_in_place(programs, pool_bytes, layer_bytes)
    one_layer_expert = 16 * 2304 * 896 * 2           # bytes of w_gate[layer]
    for name in ("decode", "chunk"):
        hlo, _ = programs[name]
        assert not _pool_sized_results(hlo, one_layer_expert // 2), name
        kernels = set(re.findall(r"%([\w.]+?)(?:\.\d+)? = \S+ custom-call\(.*"
                                 r'custom_call_target="tpu_custom_call"', hlo))
        want = {"_moe_gmm"} | ({"_paged_decode_attention"} if name == "decode"
                               else set())
        assert want <= kernels, (name, kernels)
