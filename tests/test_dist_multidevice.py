"""Multi-device distribution tests — run in a subprocess so the
``xla_force_host_platform_device_count`` flag can be set before jax init
without polluting the single-device test session."""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.multidevice  # subprocess-based: each test re-inits jax

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.abspath(REPO_SRC)
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_ring_collective_matmuls_match_reference():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.dist.overlap import ring_allgather_matmul, ring_reducescatter_matmul
        mesh = make_mesh((8,), ("model",))
        x = jax.random.normal(jax.random.key(0), (64, 32), jnp.float32)
        w = jax.random.normal(jax.random.key(1), (32, 48), jnp.float32)
        f = jax.shard_map(partial(ring_allgather_matmul, axis_name="model"), mesh=mesh,
                      in_specs=(P("model", None), P(None, "model")), out_specs=P(None, "model"))
        g = jax.shard_map(partial(ring_reducescatter_matmul, axis_name="model"), mesh=mesh,
                      in_specs=(P(None, "model"), P("model", None)), out_specs=P("model", None))
        e1 = float(jnp.abs(jax.jit(f)(x, w) - x @ w).max())
        e2 = float(jnp.abs(jax.jit(g)(x, w) - x @ w).max())
        assert e1 < 1e-4 and e2 < 1e-4, (e1, e2)
        print("OK", e1, e2)
    """)
    assert "OK" in out


def test_compressed_psum_and_error_feedback():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.dist.compress import compressed_psum
        mesh = make_mesh((8,), ("pod",))
        g = jax.random.normal(jax.random.key(2), (8, 256), jnp.float32)
        err = jnp.zeros((8, 256))
        h = jax.shard_map(partial(compressed_psum, axis_name="pod"), mesh=mesh,
                      in_specs=(P("pod", None), P("pod", None)),
                      out_specs=(P("pod", None), P("pod", None)))
        gm, ne = jax.jit(h)(g, err)
        gm = np.asarray(gm)               # every row holds the same mean
        rel = float(jnp.abs(gm[0] - g.mean(0)).max() / jnp.abs(g.mean(0)).max())
        assert rel < 0.05, rel
        # error feedback: accumulated mean over repeats converges
        gm2 = np.asarray(jax.jit(h)(g, ne)[0])
        acc = (gm[0] + gm2[0]) / 2
        rel2 = float(jnp.abs(acc - g.mean(0)).max() / jnp.abs(g.mean(0)).max())
        assert rel2 < rel + 0.01
        print("OK", rel, rel2)
    """)
    assert "OK" in out


def test_smoke_cell_compiles_on_small_mesh_and_has_collectives():
    """A reduced-config train cell lowers+compiles on a 2x4 mesh and the
    compiled module contains the expected collective kinds."""
    out = _run("""
        import jax
        from repro.launch.mesh import make_mesh
        from repro.launch.cells import build_cell, CellPlan
        from repro.analysis.hlo_collectives import collective_summary
        mesh = make_mesh((2, 4), ("data", "model"))
        cell = build_cell("yi_9b", "train_4k", mesh, smoke=True,
                          plan=CellPlan(microbatches=2, seq_shard=False, remat=True))
        c = cell.lower().compile()
        stats = collective_summary(c.as_text())
        assert "all-reduce" in stats.per_kind, stats.per_kind
        assert stats.total_bytes > 0
        print("OK", sorted(stats.per_kind))
    """, devices=8)
    assert "OK" in out


def test_elastic_restore_onto_different_mesh():
    """Checkpoint saved unsharded restores onto a 2x2 mesh with shardings."""
    out = _run("""
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointManager
        from repro.launch.mesh import make_mesh
        state = {"w": jnp.arange(64.0).reshape(8, 8), "step": jnp.asarray(3)}
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, async_save=False)
            mgr.save(3, state)
            mesh = make_mesh((2, 2), ("data", "model"))
            sh = {"w": NamedSharding(mesh, P("data", "model")),
                  "step": NamedSharding(mesh, P())}
            step, out = mgr.restore(state, shardings=sh)
            assert step == 3
            assert out["w"].sharding.is_equivalent_to(sh["w"], 2)
            assert np.array_equal(np.asarray(out["w"]), np.arange(64.0).reshape(8, 8))
        print("OK")
    """, devices=4)
    assert "OK" in out


def test_fsdp_param_specs_shard_over_data():
    out = _run("""
        import jax
        from repro.configs.base import get_config
        from repro.dist.sharding import param_pspecs
        from repro.launch.mesh import make_mesh
        from repro.models import transformer
        cfg = get_config("yi_9b")
        mesh = make_mesh((4, 2), ("data", "model"))
        shapes = jax.eval_shape(lambda k: transformer.init_params(cfg, k), jax.random.key(0))
        specs = param_pspecs(cfg, shapes, mesh, fsdp=True)
        flat = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
        n_data = sum(1 for s in flat if "data" in jax.tree.leaves(tuple(s)))
        assert n_data > 4, n_data
        print("OK", n_data)
    """, devices=8)
    assert "OK" in out
