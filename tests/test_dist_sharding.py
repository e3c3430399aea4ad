"""Fast single-process dist coverage (conftest forces 8 host devices):
spec factories, the logical-axis shard() contract, ring collectives,
compressed psum, and the slot -> executor sub-mesh bridge."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import api as graphi
from repro.configs.base import get_config
from repro.core import KNL7250
from repro.core.wavefront import recurrence_graph
from repro.dist.compress import compressed_psum
from repro.dist.executor_mesh import (
    executor_groups,
    executor_stacked_mesh,
    lane_pspec,
    plan_from_schedule,
)
from repro.dist.overlap import ring_allgather_matmul, ring_reducescatter_matmul
from repro.dist.sharding import (
    MeshCtx,
    batch_axes,
    batch_pspecs,
    cache_pspecs,
    mesh_context,
    param_pspecs,
    shard,
    use_mesh,
)
from repro.launch.mesh import make_mesh
from repro.models import transformer


@pytest.fixture(scope="module")
def mesh():
    if jax.device_count() < 8:
        pytest.skip("needs 8 host devices (conftest XLA_FLAGS)")
    return make_mesh((4, 2), ("data", "model"))


# ---------------------------------------------------------------------------
# sharding: context + spec factories
# ---------------------------------------------------------------------------

def test_shard_is_noop_without_mesh():
    x = jnp.ones((4, 4))
    assert mesh_context() is None
    assert shard(x, "batch", "model") is x


def test_shard_constrains_and_drops_indivisible(mesh):
    ctx = MeshCtx(mesh, batch_axes(mesh, 8))
    x = jnp.zeros((8, 6, 4))
    with use_mesh(ctx):
        y = jax.jit(lambda a: shard(a, "batch", None, "model"))(x)
        # dim0: 8 % data(4) == 0 -> sharded; dim2: 4 % model(2) == 0 -> sharded
        assert y.sharding.is_equivalent_to(
            jax.sharding.NamedSharding(mesh, P("data", None, "model")), 3
        )
        # indivisible dims drop their axis instead of erroring
        z = jnp.zeros((3, 5))
        w = jax.jit(lambda a: shard(a, "batch", "model"))(z)
        assert w.sharding.is_fully_replicated
    assert mesh_context() is None


def test_batch_axes_divisibility(mesh):
    assert batch_axes(mesh, 256) == ("data",)
    assert batch_axes(mesh, 2) == ()      # 2 % 4 != 0
    assert batch_axes(mesh, 1) == ()      # long_500k: B=1 never shards


def test_param_pspecs_megatron_rules(mesh):
    cfg = get_config("yi_9b")
    shapes = jax.eval_shape(lambda k: transformer.init_params(cfg, k), jax.random.key(0))
    specs = param_pspecs(cfg, shapes, mesh)
    assert specs["embed"] == P("model", None)
    assert specs["layers"]["attn"]["wq"] == P(None, None, "model")
    assert specs["layers"]["attn"]["wo"] == P(None, "model", None)
    assert specs["layers"]["mlp"]["w_down"] == P(None, "model", None)
    assert specs["layers"]["ln1"] == P(None, None)


def test_param_pspecs_fsdp_shards_over_data(mesh):
    cfg = get_config("yi_9b")
    shapes = jax.eval_shape(lambda k: transformer.init_params(cfg, k), jax.random.key(0))
    specs = param_pspecs(cfg, shapes, mesh, fsdp=True)
    flat = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
    n_data = sum(1 for s in flat if "data" in jax.tree.leaves(tuple(s)))
    assert n_data > 4, n_data


def test_batch_and_cache_pspecs(mesh):
    cfg = get_config("yi_9b", smoke=True)
    bp = batch_pspecs({"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32)}, mesh, 8)
    assert bp["tokens"] == P("data", None)
    cache = jax.eval_shape(lambda: transformer.init_cache(cfg, 8, 64))
    cp = cache_pspecs(cfg, cache, mesh, 8)
    assert cp["len"] == P()
    # stacked [L, B, C, H, hd]: batch over data, seq slots over model
    assert tuple(cp["layers"]["k"])[:3] == (None, "data", "model")


# ---------------------------------------------------------------------------
# collectives (in-process; the subprocess suite re-proves under fresh jax)
# ---------------------------------------------------------------------------

def test_ring_matmuls_match_reference_inprocess():
    if jax.device_count() < 8:
        pytest.skip("needs 8 host devices")
    m = make_mesh((8,), ("model",))
    x = jax.random.normal(jax.random.key(0), (64, 32), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (32, 48), jnp.float32)
    f = jax.shard_map(partial(ring_allgather_matmul, axis_name="model"), mesh=m,
                  in_specs=(P("model", None), P(None, "model")), out_specs=P(None, "model"))
    g = jax.shard_map(partial(ring_reducescatter_matmul, axis_name="model"), mesh=m,
                  in_specs=(P(None, "model"), P("model", None)), out_specs=P("model", None))
    np.testing.assert_allclose(jax.jit(f)(x, w), x @ w, atol=1e-4)
    np.testing.assert_allclose(jax.jit(g)(x, w), x @ w, atol=1e-4)


def test_compressed_psum_error_feedback_inprocess():
    if jax.device_count() < 8:
        pytest.skip("needs 8 host devices")
    m = make_mesh((8,), ("pod",))
    g = jax.random.normal(jax.random.key(2), (8, 128), jnp.float32)
    h = jax.shard_map(partial(compressed_psum, axis_name="pod"), mesh=m,
                  in_specs=(P("pod", None), P("pod", None)),
                  out_specs=(P("pod", None), P("pod", None)))
    gm, ne = jax.jit(h)(g, jnp.zeros_like(g))
    gm = np.asarray(gm)               # every row holds the same mean
    ref = g.mean(0)
    rel = float(jnp.abs(gm[0] - ref).max() / jnp.abs(ref).max())
    assert rel < 0.05
    gm2 = np.asarray(jax.jit(h)(g, ne)[0])
    rel2 = float(jnp.abs((gm[0] + gm2[0]) / 2 - ref).max() / jnp.abs(ref).max())
    assert rel2 < rel + 0.01


# ---------------------------------------------------------------------------
# executor mesh bridge
# ---------------------------------------------------------------------------

def test_executor_groups_are_disjoint_and_cover(mesh):
    groups = executor_groups(mesh, 4)
    ids = [g.device_ids for g in groups]
    flat = [d for i in ids for d in i]
    assert len(flat) == len(set(flat)) == 8
    for g in groups:
        assert dict(g.mesh.shape) == {"data": 4, "model": 1} or \
               dict(g.mesh.shape) == {"data": 1, "model": 2}


def test_executor_stacked_mesh_splits_axis(mesh):
    sm = executor_stacked_mesh(mesh, 2, axis="model")
    assert sm.axis_names == ("data", "executor", "model")
    assert sm.shape["executor"] == 2 and sm.shape["model"] == 1
    assert lane_pspec(3) == P("executor", None, None)
    # a slot-stacked array actually places lanes on disjoint devices
    x = jnp.zeros((2, 4, 4))
    y = jax.device_put(x, jax.sharding.NamedSharding(sm, lane_pspec(3)))
    assert y.sharding.shard_shape(x.shape) == (1, 4, 4)
    lane_devs = [
        {s.device.id for s in y.addressable_shards if s.index[0] == slice(i, i + 1)}
        for i in range(2)
    ]
    assert lane_devs[0] and lane_devs[1] and not (lane_devs[0] & lane_devs[1])


def test_plan_from_schedule_slot_lanes(mesh):
    g = recurrence_graph(4, 6, flops_per_cell=1e6, bytes_per_cell=1e4)
    exe = graphi.compile(g, hw=KNL7250, backend="sim", n_executors=4, team_size=8)
    sched = exe.schedule
    plan = plan_from_schedule(g, sched, mesh, axis="data")
    assert sorted(plan.placement) == sorted(g.names)
    assert plan.n_executors == 4
    for slot in plan.slots:
        lanes = [plan.placement[op] for op in slot]
        assert len(set(lanes)) == len(lanes)        # one op per executor
        assert all(l < sched.n_executors for l in lanes)
    # deps never land in the same slot (barrier semantics)
    slot_of = {op: s for s, ops in enumerate(plan.slots) for op in ops}
    for n in g.names:
        for d in g.predecessors(n):
            assert slot_of[d] < slot_of[n]


def test_executable_static_plan_end_to_end(mesh):
    from repro.core import TPUV5E

    g = recurrence_graph(3, 5, flops_per_cell=1e9, bytes_per_cell=1e6)
    exe = graphi.compile(g, hw=TPUV5E, backend="sim", n_workers=8)
    plan = exe.static_plan(mesh, axis="data")
    assert sorted(plan.placement) == sorted(g.names)
    assert 1 <= plan.n_executors <= 4
