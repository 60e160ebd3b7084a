"""The benchmark's copy of the model-FLOP arithmetic agrees with the
program's for every registered configuration."""
import pytest

from bench import flops
from repro.configs import get_config, list_configs
from repro.core import flops as program_flops


@pytest.mark.parametrize("name", list_configs())
@pytest.mark.parametrize("b,s", [(1, 512), (8, 2048)])
def test_model_flops_match_program(name, b, s):
    cfg = get_config(name)
    assert flops.model_flops_train(cfg, b, s) == pytest.approx(
        program_flops.model_flops_train(cfg, b, s), rel=1e-12)


def test_flash_work_counts_six_causal_matmuls():
    f, nbytes = flops.flash_attention_work(1, 512, 16, 16, 64)
    assert f == 6 * 2 * 16 * 512 * 512 * 64 / 2
    # q, o, do, dq at 1 MiB each; k, v, dk, dv at 1 MiB each: 12 tensor
    # passes of 1 MiB plus the fp32 row statistic written and read
    assert nbytes == 12 * 512 * 16 * 64 * 2 + 2 * 16 * 512 * 4
