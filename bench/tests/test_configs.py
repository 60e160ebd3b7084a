"""Each configuration file equals its registered ``ModelConfig`` in every
key it does not list as reduced or set from the source, nested blocks key
by key; its ``reference`` names a module of ``bench/models/``; and what
the harness builds from it is what the file states."""
import dataclasses
import importlib.util
import json
import pathlib

import pytest

from bench import harness
from repro.configs import get_config

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: c for c in BENCH["configs"]}


def _plain(v):
    """A config value as the file would state it: a list for a tuple, a
    dict for a nested dataclass."""
    if dataclasses.is_dataclass(v):
        return dataclasses.asdict(v)
    return list(v) if isinstance(v, tuple) else v


def _same(got, value) -> bool:
    """A nested block matches where every key it states does."""
    got = _plain(got)
    if isinstance(value, dict):
        return isinstance(got, dict) and all(
            k in got and got[k] == v for k, v in value.items())
    return got == value


def _assert_states(cfg, model, skip=()):
    for key, value in model.items():
        if key in skip or not hasattr(cfg, key):
            continue
        got = _plain(getattr(cfg, key))
        if isinstance(value, dict):
            assert isinstance(got, dict), f"{key}: registered {got!r}"
            for k, v in value.items():
                assert got.get(k) == v, f"{key}.{k}: built {got.get(k)!r}, file {v!r}"
        else:
            assert got == value, f"{key}: built {got!r}, file {value!r}"


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
        if key in changed:
            assert not _same(getattr(base, key), value), \
                f"{key} is listed as changed but equals the registered value"
    _assert_states(base, cf["model"], skip=changed)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_config_names_a_reference_module(name):
    cf = json.loads((ROOT / CONFIGS[name]["file"]).read_text())
    path = ROOT / "bench" / "models" / f"{cf['reference']}.py"
    assert path.is_file(), path
    assert importlib.util.find_spec(f"bench.models.{cf['reference']}")
    arch = harness.load_arch(cf["reference"])
    for name_ in ("layer_kinds", "param_shapes", "fan_in", "DECAYED",
                  "embed", "layer", "head_loss", "to_program",
                  "from_program", "model_flops_train"):
        assert hasattr(arch, name_), name_


@pytest.mark.parametrize("name", sorted(w["name"] for w in BENCH["workloads"]))
def test_cell_builds_the_stated_model(name):
    cell = harness.find_cell(name)
    cfg = harness.model_config(cell.config, cell.job)
    _assert_states(cfg, cell.config["model"])
    assert cfg.attn_impl == cell.job["attn_impl"]
    assert cell.arch is harness.load_arch(cell.config["reference"])


@pytest.mark.parametrize("base,moe", [
    ("granite-moe-1b-a400m", {"num_experts": 4, "top_k": 2}),
    ("qwen1.5-0.5b", {"num_experts": 8, "top_k": 2, "d_ff": 128,
                      "capacity_factor": 4.0})])
def test_nested_block_builds_its_dataclass(base, moe):
    """A ``moe`` block replaces the named keys of the registered
    ``MoEConfig``, or builds one where the registered value is None."""
    registered = get_config(base).moe
    cfg = harness.model_config({"base": base, "model": {"moe": moe}},
                               {"attn_impl": "reference"})
    assert type(cfg.moe).__name__ == "MoEConfig"
    _assert_states(cfg, {"moe": moe})
    if registered is not None:
        untouched = {k: v for k, v in dataclasses.asdict(registered).items()
                     if k not in moe}
        _assert_states(cfg, {"moe": untouched})
