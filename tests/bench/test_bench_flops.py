"""Operation and byte counts against hand arithmetic."""
import json
from pathlib import Path

import pytest

from bench.harness import flops

REPO = Path(__file__).resolve().parents[2]


DANUBE = json.loads((REPO / "bench/configs/danube3.json").read_text())


def test_danube3_param_count():
    # per layer: q 3840x3840, k and v 3840x960, o 3840x3840, mlp 3 x 3840x10240
    layer = 3840 * 3840 * 2 + 3840 * 960 * 2 + 3 * 3840 * 10240
    assert flops.layer_matmul_params(DANUBE) == layer == 154_828_800
    assert flops.matmul_params(DANUBE) == 24 * layer + 3840 * 32000


def test_decode_token_flops():
    # 2 per weight, plus q.k and p.v: 4 x 24 layers x 32 heads x 120 x 1000
    want = 2 * (24 * 154_828_800 + 3840 * 32000) + 4 * 24 * 32 * 120 * 1000
    assert flops.forward_token_flops(DANUBE, 1000) == want
    # the window caps what a query attends to
    assert flops.attended(DANUBE, 5000) == 4096


def test_paged_decode_attention_cost():
    f, b = flops.paged_decode_attention_cost(DANUBE, [100, 300])
    assert f == 4 * 32 * 120 * 400
    # K and V of 400 positions, 8 heads of 120, bf16; q and out of 2 rows
    assert b == 400 * 8 * 120 * 2 * 2 + 2 * 2 * 32 * 120 * 2
    assert flops.least_time(f, b, 197e12, 819e9) == pytest.approx(b / 819e9)


def test_train_token_flops():
    m = dict(DANUBE, sliding_window=None)
    # mean causal context over 4 positions: (1+2+3+4)/4
    attn = 4 * 24 * 32 * 120 * 2.5
    assert flops.train_token_flops(m, 4) == pytest.approx(
        3 * (2 * flops.matmul_params(m) + attn))
