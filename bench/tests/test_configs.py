"""Each configuration file equals its registered ``ModelConfig`` in every
key it does not list as reduced or set from the source, and what the
harness builds from it is what the file states."""
import dataclasses
import json
import pathlib

import pytest

from bench import harness
from repro.configs import get_config

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: c for c in BENCH["configs"]}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_config_matches_registered(name):
    cf = json.loads((ROOT / CONFIGS[name]["file"]).read_text())
    base = get_config(cf["base"])
    fields = {f.name for f in dataclasses.fields(base)}
    reduced = cf["reduced"]
    changed = {r["model_key"] for r in reduced.values()} | set(cf["set_from_source"])
    assert set(CONFIGS[name]["reduced"]) == set(reduced)
    for src_key, r in reduced.items():
        assert cf["published"][src_key] == r["published"]
        assert cf["model"][r["model_key"]] != r["published"]
    for key, value in cf["model"].items():
        assert key in fields, f"{key} is no ModelConfig field"
        got = getattr(base, key)
        got = list(got) if isinstance(got, tuple) else got
        if key in changed:
            assert got != value, f"{key} is listed as changed but equals the registered value"
        else:
            assert got == value, f"{key}: registered {got!r}, file {value!r}"


@pytest.mark.parametrize("name", sorted(w["name"] for w in BENCH["workloads"]))
def test_cell_builds_the_stated_model(name):
    cell = harness.find_cell(name)
    cfg = harness.model_config(cell.config, cell.job)
    for key, value in cell.config["model"].items():
        if hasattr(cfg, key):
            got = getattr(cfg, key)
            assert (list(got) if isinstance(got, tuple) else got) == value
    assert cfg.attn_impl == cell.job["attn_impl"]
