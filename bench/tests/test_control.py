"""The control, the reference computed on float8-rounded operands in the
program's place, is not correct under the tiny size's limits; the program at
the same size is."""
import jax
import pytest

from bench import check, control
from tiny import tiny_cell


@pytest.mark.parametrize("seed", [7, 2**33 + 9])
def test_control_fails_program_passes(seed):
    cell = tiny_cell()
    out = control.readings(cell, seed, jax.devices()[:1], log=lambda s: None)
    limits = {k: v for k, v in cell.limits.items() if k != "window_compiles"}
    assert check.judge(out["program"], limits)[0], out["program"]
    assert not check.judge(out["control"], limits)[0], out["control"]
