"""A cell with another architecture needs new files only: the test-only
reference ``moe_ref`` of the program's MoE block, under alternating
windowed and global attention, goes through ``harness.run`` at a tiny size
on the CPU and is correct; with the router's gates left unnormalised in a
copy of the program's routing it is not. Neither the harness, the
reference's trainer nor the entry names the module."""
import pathlib
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from repro.models import moe
from tiny import PEAKS, tiny_moe_cell

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _unnormalised_route(p, x, cfg):
    """``models.moe.route`` without the division of the top-k gates by
    their sum."""
    e = cfg.moe
    logits = (x.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, e.top_k)
    f = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, e.num_experts, dtype=jnp.float32), axis=2),
        axis=(0, 1))
    pbar = jnp.mean(probs, axis=(0, 1))
    return gates, idx, e.num_experts * jnp.sum(f * pbar)


def _run(traced):
    cell = tiny_moe_cell()
    cell.per_layer = [{"name": "mfu", "unit": "%"}]
    return harness.run(cell, 2**33 + 41, 0.5, traced,
                       t0=time.perf_counter(), devices=jax.devices()[:1],
                       log=lambda s: None, peaks=PEAKS)


def test_moe_cell_is_correct():
    r = _run(traced=True)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["checks"]["window_compiles"]["value"] == 0
    assert r["metrics"]["mfu"]["value"] > 0


def test_unnormalised_gates_are_not_correct(monkeypatch):
    monkeypatch.setattr(moe, "route", _unnormalised_route)
    r = _run(traced=False)
    assert r["correct"] is False, r["checks"]


@pytest.mark.parametrize("path", ["bench/harness.py", "bench/reference.py",
                                  "bench/entries/executor.py"])
def test_no_harness_file_names_the_module(path):
    assert "moe_ref" not in (ROOT / path).read_text()
