"""Ahead-of-time compiles for a described TPU v5e chip (none attached).

The TPU compiler is installed alongside jaxlib, so the Pallas kernels of the
serving path can be compiled for a chip that is only described: what the
Mosaic compiler would refuse on the chip (block shapes off the tiling, too
much VMEM) fails here instead.  The topology is described inside a fixture,
never at import: only one process may load the TPU library, and a worker
that cannot describe the chip skips these tests rather than breaking the
collection of the others.  Keep every such compile in this one file.
"""
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

    pages = spec((batch * n_pt, page_size, cfg.n_kv_heads, hd), cfg.dtype)
    args = (spec((batch, 1, cfg.n_heads, hd), cfg.dtype), pages, pages,
            spec((batch, n_pt), jnp.int32), spec((batch,), jnp.int32))
    fn = partial(paged_decode_attention, window=cfg.sliding_window or None,
                 use_kernel=True, interpret=False)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
