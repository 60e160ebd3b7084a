"""chip_smoke.py on the CPU at a tiny size: its one-chip phases end to
end, its four-chip phase on four virtual devices, and its refusal to run
without a TPU."""
import dataclasses
import importlib.util
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny_cfg():
    from repro.configs import get_config
    return dataclasses.replace(get_config("qwen1.5-0.5b").reduced(),
                               num_layers=4)


def test_refuses_without_tpu(capsys):
    assert _chip_smoke().main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


@pytest.mark.slow
def test_one_chip_phases_on_cpu(capsys):
    cs = _chip_smoke()
    cs.one_chip(_tiny_cfg(), seq=32, batch=8, steps=2, kernel_marker=None,
                kernel_shapes=((1, 32, 4, 2, 32),))
    out = capsys.readouterr().out
    for phase in ("kernel", "bpipe step 1", "1f1b step 1", "flash step 0"):
        assert phase in out
    assert "1f1b step-0 loss" in out and "equals bpipe's" in out


FOUR_CHIPS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses, sys
    sys.path.insert(0, %r)
    import jax
    import chip_smoke as cs
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config("qwen1.5-0.5b").reduced(),
                              num_layers=4)
    cs.four_chips(cfg, jax.devices()[:4], seq=32, num_micro=4)
    print("FOUR_OK")
""") % ROOT


@pytest.mark.slow
def test_four_chip_phase_on_virtual_devices():
    r = subprocess.run([sys.executable, "-c", FOUR_CHIPS],
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert "FOUR_OK" in r.stdout, r.stdout + r.stderr
    assert "BPipe stash adds collective-permutes" in r.stdout
