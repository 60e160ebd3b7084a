"""Each entry runs a whole cell end to end at a tiny size on the CPU, and
``bench/run.py`` refuses to print a result without a TPU."""
import json
import pathlib
import subprocess
import sys
import time

import jax
import pytest

from bench import harness
from tiny import PEAKS, tiny_cell

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("traced", [False, True])
def test_executor_entry_end_to_end(traced):
    cell = tiny_cell()
    lines = []
    r = harness.run(cell, 2**33 + 17, 1.0, traced, t0=time.perf_counter(),
                    devices=jax.devices()[:1], log=lines.append, peaks=PEAKS)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"] and list(r)[-1] == "checks"
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["checks"]["window_compiles"]["value"] == 0
    if traced:
        assert "breakdown" in r and r["device"]["window_s"] > 0
        assert "executor_host_ms" in r["metrics"]
        # the CPU trace has no TPU plane: no device-time metric is made up
        assert "flash_roofline" not in r["metrics"]
    else:
        assert set(r["metrics"]) == {m["name"] for m in BENCH["end_to_end"]}
    assert any(l.startswith("store: evictions") for l in lines)
    assert any(l.startswith("planner predicted step time") for l in lines)


def test_same_seed_same_inputs():
    from bench import traffic
    a = traffic.make_batch(512, 2, 16, 2**33 + 1, 4, 1.3)
    b = traffic.make_batch(512, 2, 16, 2**33 + 1, 4, 1.3)
    c = traffic.make_batch(512, 2, 16, 1, 4, 1.3)
    assert (a["tokens"] == b["tokens"]).all()
    assert not (a["tokens"] == c["tokens"]).all()
    assert (a["tokens"][:, 1:] == a["labels"][:, :-1]).all()


def test_run_refuses_without_tpu():
    cell = BENCH["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=ROOT, capture_output=True,
        text=True, env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "Nothing was run" in p.stderr
