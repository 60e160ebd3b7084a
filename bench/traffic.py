"""The benchmark's one traffic generator: seeded next-token training batches.

Copied from ``repro.data.pipeline.make_batch`` (a zipf-flavoured token
marginal, a pure function of (seed, step)) so that no later change to the
program can change the benchmark's input. A traffic mix is a data file
``bench/traffic/<name>.json`` whose ``rows``, ``seq`` and ``zipf_a`` this
module reads; nothing here is specific to one mix.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def _rng(seed: int, step: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, step]))


def make_batch(vocab: int, rows: int, seq: int, seed: int, step: int,
               zipf_a: float) -> Dict[str, np.ndarray]:
    """One global batch of ``rows`` sequences of ``seq`` tokens: tokens and
    the next-token labels, every label valid."""
    z = _rng(seed, step).zipf(zipf_a, size=(rows, seq + 1))
    toks = (z % vocab).astype(np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
