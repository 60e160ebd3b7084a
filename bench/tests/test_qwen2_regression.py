"""The Qwen1.5 reference (``bench/models/qwen2.py`` through
``bench/reference.py``) gives, at the tiny size, the weights and readings
recorded from the reference as it stood before the architectures were
split into ``bench/models/`` (commit 10b9d28, ``data/qwen2_tiny_readings.json``,
two seeds): the same weights bit for bit, and each reading within 1e-6."""
import hashlib
import json
import pathlib

import jax
import numpy as np
import pytest

from bench import reference, traffic
from bench.models import qwen2
from tiny import tiny_cell

DATA = json.loads((pathlib.Path(__file__).resolve().parent / "data"
                   / "qwen2_tiny_readings.json").read_text())


@pytest.mark.parametrize("seed", sorted(DATA["seeds"]))
def test_weights_and_readings_as_recorded(seed):
    want = DATA["seeds"][seed]
    cell = tiny_cell()
    m, job = cell.config["model"], cell.job
    assert cell.arch is qwen2
    for k, v in DATA["model"].items():
        assert m[k] == v, k
    for k, v in DATA["job"].items():
        assert job[k] == v, k
    key = reference.seed_key(int(seed))
    params = jax.jit(lambda k: reference.init_params(qwen2, m, k))(key)
    got = {".".join(jax.tree_util.keystr((p,)).strip("[]'") for p in path):
           hashlib.sha256(np.asarray(a, np.float32).tobytes()).hexdigest()
           for path, a in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert got == want["init_sha256"]
    batches = [traffic.make_batch(m["vocab_size"], job["rows"], job["seq"],
                                  int(seed), s, job["zipf_a"])
               for s in range(job["check_steps"])]
    r = reference.train_readings(qwen2, m, job["optimizer"], batches, key)
    assert r["loss"] == pytest.approx(want["loss"], rel=1e-6)
    for k in ("grad", "change"):
        assert set(r[k]) == set(want[k])
        for leaf, v in want[k].items():
            assert r[k][leaf] == pytest.approx(v, rel=1e-6), (k, leaf)
