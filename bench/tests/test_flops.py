"""The benchmark's copy of the model-FLOP arithmetic agrees with the
program's for every registered configuration, and each cell's
architecture module counts the same model FLOPs for its configuration."""
import json
import pathlib

import pytest

from bench import flops, harness
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


BENCH = json.loads((pathlib.Path(__file__).resolve().parents[2]
                    / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", sorted(w["name"] for w in BENCH["workloads"]))
@pytest.mark.parametrize("b,s", [(1, 512), (8, 512), (8, 2048)])
def test_module_flops_match_copy(name, b, s):
    cell = harness.find_cell(name)
    cfg = harness.model_config(cell.config, cell.job)
    assert cell.arch.model_flops_train(cell.config["model"], b, s) \
        == flops.model_flops_train(cfg, b, s)
