"""Cell planner invariants (no devices needed — pure plan logic)."""
import pytest

from repro.configs.base import SHAPES, get_config, list_archs
from repro.launch.cells import FULL_ATTENTION_ARCHS, cell_matrix, plan_for, skip_reason


class FakeMesh:
    """Just enough of a Mesh for plan_for: shape mapping + device count."""

    def __init__(self, shape: dict):
        self.shape = shape
        self.axis_names = tuple(shape)

        class _D:
            size = 1

        self._d = _D()
        self._d.size = 1
        for v in shape.values():
            self._d.size *= v

    @property
    def devices(self):
        return self._d


MESH = FakeMesh({"data": 16, "model": 16})


def test_cell_matrix_is_40_cells():
    """The assignment's 40 cells (10 archs x 4 shapes), plus mellum2's 4."""
    cells = cell_matrix()
    assert len(cells) == 44
    assert len({a for a, _ in cells}) == 11


def test_long_500k_skips_exactly_the_full_attention_archs():
    skipped = {a for a, s in cell_matrix() if skip_reason(a, s)}
    assert {a.replace("_", "-") for a in skipped} == FULL_ATTENTION_ARCHS
    # and never for other shapes
    for a, s in cell_matrix():
        if s != "long_500k":
            assert skip_reason(a, s) is None


@pytest.mark.parametrize("arch", list_archs())
@pytest.mark.parametrize("shape", ["train_4k", "prefill_32k", "decode_32k"])
def test_plans_respect_divisibility(arch, shape):
    cfg = get_config(arch)
    plan = plan_for(cfg, SHAPES[shape], MESH)
    B = SHAPES[shape].global_batch
    assert B % plan.microbatches == 0
    # cap: per-microbatch batch still divides the dp axis
    assert plan.microbatches <= max(1, B // 16)
    if SHAPES[shape].kind == "decode":
        assert plan.microbatches == 1 and not plan.remat


def test_fsdp_triggers_for_large_models_only():
    big = plan_for(get_config("command-r-plus-104b"), SHAPES["train_4k"], MESH)
    small = plan_for(get_config("granite-moe-1b-a400m"), SHAPES["train_4k"], MESH)
    assert big.fsdp and not small.fsdp


def test_plan_extra_overrides_config():
    from repro.launch.cells import CellPlan

    plan = CellPlan(extra={"attn_q_chunk": 256})
    # build_cell applies extra via cfg.reduced — verify the field exists
    cfg = get_config("gemma-2b").reduced(**plan.extra)
    assert cfg.attn_q_chunk == 256
