"""The comparison that decides ``correct``: sound runs pass; the float8
control fails; and a run with its timed path broken underneath fails, for
each fault the cell can have.  Driven through the whole harness at toy
widths on the CPU (training on four virtual devices)."""
import json

import jax
import pytest

from bench import control, run
from bench.harness import check, spec

SERVING = ["toy.decode", "toy.prefix"]
ALL = SERVING + ["toy.train"]


def _cpu(n):
    return jax.devices()[:n]


def _run(root, cell, seed, capsys) -> dict:
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds", "1"],
                  root=root, require_chips=_cpu)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ALL)
def test_control_fails_and_program_passes(toy_root, cell):
    c = spec.load_cell(cell, toy_root)
    limits = c.limits["limits"]
    for r in control.readings(c, [11, 2**31 + 5], 2.0, _cpu(c.chips),
                              toy_root, log=lambda _: None):
        assert check.judge(r["program"], limits)[0], r
        assert not check.judge(r["control"], limits)[0], r


@pytest.mark.parametrize("cell", ALL)
def test_sound_run_is_correct(toy_root, cell, capsys):
    out = _run(toy_root, cell, 3, capsys)
    assert out["correct"] is True, out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert "setup_s" in out["metrics"]
    if cell == "toy.train":
        assert out["metrics"]["train_tok_s"]["value"] > 0


@pytest.mark.parametrize("cell", SERVING)
def test_altered_token_is_caught(toy_root, cell, capsys, monkeypatch):
    from repro.serve.paged import PagedEngine

    emit = PagedEngine._emit
    vocab = spec.load_cell(cell, toy_root).config["vocab_size"]

    def altered(self, slot, token):
        if len(self.slots[slot].output) == 1:      # every request's 2nd token
            token = (token + 1) % vocab
        return emit(self, slot, token)

    monkeypatch.setattr(PagedEngine, "_emit", altered)
    out = _run(toy_root, cell, 4, capsys)
    assert out["correct"] is False


def _unchanged(make_run_step):
    def make(*a, **k):
        step = make_run_step(*a, **k)

        def run_step(state, batch):
            _, metrics = step(jax.tree.map(lambda x: x.copy(), state), batch)
            return state, metrics
        return run_step
    return make


def _half_batch(lm_loss):
    def loss(cfg, params, batch, **k):
        half = {n: x[: x.shape[0] // 2] for n, x in batch.items()}
        return lm_loss(cfg, params, half, **k)
    return loss


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_training_fault_is_caught(toy_root, fault, capsys, monkeypatch):
    import repro.launch.train as launch_train
    import repro.models.api as model_api

    if fault == "state_unchanged":
        monkeypatch.setattr(launch_train, "make_run_step",
                            _unchanged(launch_train.make_run_step))
    else:
        monkeypatch.setattr(model_api, "lm_loss", _half_batch(model_api.lm_loss))
    out = _run(toy_root, "toy.train", 6, capsys)
    assert out["correct"] is False, out["checks"]


def test_judge_missing_reading_fails():
    ok, out = check.judge({"max_logit_gap": None}, {"max_logit_gap": 1.0})
    assert not ok and out["max_logit_gap"]["limit"] == 1.0
    ok, _ = check.judge({"max_logit_gap": float("nan")}, {"max_logit_gap": 1.0})
    assert not ok
