"""A run whose timed path is broken underneath comes out not correct:
once for each fault the cell can have. Drives ``harness.run`` at a tiny
size on the CPU with the limits of that size (``tiny.TINY_LIMITS``)."""
import time

import jax
import numpy as np
import pytest

from bench import harness
from repro.optim import adam
from repro.pipeline import executor
from tiny import PEAKS, tiny_cell


def _state_unchanged(monkeypatch):
    real = adam.update

    def update(params, grads, state, tcfg, **kw):
        _, new_state, metrics = real(params, grads, state, tcfg, **kw)
        return params, new_state, metrics
    monkeypatch.setattr(adam, "update", update)


def _feed(monkeypatch, change):
    real = executor.PipelineExecutor.step

    def step(self, params, batch, **kw):
        batch = {k: np.array(v) for k, v in batch.items()}
        return real(self, params, change(batch), **kw)
    monkeypatch.setattr(executor.PipelineExecutor, "step", step)


def _half_batch(monkeypatch):
    def change(b):
        h = b["tokens"].shape[0] // 2
        return {k: v[:h].repeat(2, axis=0) for k, v in b.items()}
    _feed(monkeypatch, change)


def _row_altered(monkeypatch):
    def change(b):
        b["tokens"][0] = (b["tokens"][0] + 1) % 512
        return b
    _feed(monkeypatch, change)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "row_altered": _row_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    r = harness.run(tiny_cell(), 2**32 + 3, 0.5, False,
                    t0=time.perf_counter(), devices=jax.devices()[:1],
                    log=lambda s: None, peaks=PEAKS)
    assert r["correct"] is False, r["checks"]
