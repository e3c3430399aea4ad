"""``chip_smoke.py`` on the CPU: both phases at SMOKE size (every check holds
but the one only the chip can pass), and its refusal to run without a TPU."""
import importlib.util
from pathlib import Path

import jax
import pytest

from repro.configs.base import get_config
from repro.launch.mesh import make_mesh

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("argv", [[], ["--four-chip"]], ids=["serve", "four-chip"])
def test_main_fails_without_a_tpu(chip_smoke, capsys, argv):
    assert chip_smoke.main(argv) != 0
    out = capsys.readouterr()
    assert out.out == ""                       # no result line, nothing run
    assert "needs a TPU" in out.err


def test_serve_phase_at_smoke_size(chip_smoke, monkeypatch):
    # the Pallas kernel on the decode path, in interpret mode off the chip
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    cfg = get_config(chip_smoke.SERVE_ARCH, smoke=True)
    res = chip_smoke.serve_phase(cfg, seed=0)
    assert res["kernel_hlo"], "the decode graph lost its kernel node"
    assert len(res["reference"]) == chip_smoke.N_REF_REQUESTS
    # interpret mode lowers to plain HLO: only the chip passes that check
    assert sorted(chip_smoke.serve_failures(res, cfg)) == sorted(
        f"decode node {n} compiled without tpu_custom_call"
        for n in res["kernel_hlo"])


def test_train_phase_at_smoke_size(chip_smoke):
    mesh = make_mesh(chip_smoke.TRAIN_MESH, ("data", "model"),
                     devices=jax.devices()[:4])
    res = chip_smoke.train_phase(get_config(chip_smoke.TRAIN_ARCH, smoke=True),
                                 mesh, seed=0)
    assert len(res["losses"]) == chip_smoke.N_STEPS
    # CPU devices report no memory stats: only the chip passes that check
    assert chip_smoke.train_failures(res) == ["a device reports no memory stats"]
