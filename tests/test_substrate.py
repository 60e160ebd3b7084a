"""Data pipeline, optimizer, checkpointing, sharding rules."""
import dataclasses
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.configs import ASSIGNED, get_config, list_configs
from repro.configs.base import TrainConfig
from repro.checkpoint import ckpt
from repro.data.pipeline import DataConfig, make_batch
from repro.models import model as M
from repro.optim import adam
from repro.sharding import rules


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------
def test_data_deterministic_and_shaped():
    cfg = get_config("internvl2-1b").reduced()
    dc = DataConfig(batch=4, seq_len=32, seed=1)
    b1, b2 = make_batch(cfg, dc, 5), make_batch(cfg, dc, 5)
    for k in b1:
        np.testing.assert_array_equal(b1[k], b2[k])
    b3 = make_batch(cfg, dc, 6)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    n_text = 32 - cfg.num_prefix_embeds
    assert b1["tokens"].shape == (4, n_text)
    assert b1["prefix_embeds"].shape == (4, cfg.num_prefix_embeds, cfg.d_model)
    assert b1["tokens"].max() < cfg.vocab_size and b1["tokens"].min() >= 0


def test_data_encdec():
    cfg = get_config("whisper-small").reduced()
    b = make_batch(cfg, DataConfig(batch=2, seq_len=16), 0)
    assert "enc_embeds" in b and b["enc_embeds"].shape[0] == 2


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------
def test_adam_minimizes_quadratic():
    tcfg = TrainConfig(learning_rate=0.1, warmup_steps=0, steps=100,
                       weight_decay=0.0, grad_clip=0.0)
    params = {"x": jnp.array([5.0, -3.0])}
    state = adam.init(params)
    for _ in range(150):
        grads = jax.grad(lambda p: jnp.sum(p["x"] ** 2))(params)
        params, state, _ = adam.update(params, grads, state, tcfg)
    # cosine decay floors the lr at 10%, so convergence is approximate
    assert float(jnp.max(jnp.abs(params["x"]))) < 0.5


def test_grad_clip():
    tcfg = TrainConfig(learning_rate=1e-3, grad_clip=1.0, warmup_steps=0)
    params = {"x": jnp.zeros(3)}
    state = adam.init(params)
    big = {"x": jnp.array([1e6, 1e6, 1e6])}
    _, _, m = adam.update(params, big, state, tcfg)
    assert float(m["grad_norm"]) > 1e6  # reported pre-clip


def test_lr_schedule_warmup_and_decay():
    tcfg = TrainConfig(learning_rate=1.0, warmup_steps=10, steps=100)
    lrs = [float(adam.lr_schedule(tcfg, s)) for s in range(100)]
    assert lrs[0] < lrs[9] <= 1.0
    assert lrs[-1] < lrs[20]


#: Leaves that are not weight matrices: norm scales and biases (any leaf
#: under a ``*norm*`` key), biases, the router's selection bias and the
#: RG-LRU's per-channel decay. A leaf name missing from both this set and
#: ``adam.DECAYED`` fails the test below.
UNDECAYED = {"bias", "b", "ba", "bx", "bif", "bq", "bk", "bv", "conv_b",
             "route_bias", "lam"}


def _leaf_names(cfg):
    shapes = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        yield keys, leaf


@pytest.mark.parametrize("name", list_configs())
def test_weight_decay_applies_to_weight_matrices_only(name):
    """Every registered config: each leaf is a weight matrix whose name
    Adam decays, or a norm scale or bias, which it does not."""
    for keys, leaf in _leaf_names(get_config(name)):
        plain = any("norm" in k for k in keys[:-1]) or keys[-1] in UNDECAYED
        if plain:
            assert keys[-1] not in adam.DECAYED, keys
        else:
            assert keys[-1] in adam.DECAYED and leaf.ndim >= 2, keys


def test_weight_decay_skips_stacked_norms_and_selection_bias():
    tcfg = TrainConfig(learning_rate=1.0, warmup_steps=0, steps=10,
                       weight_decay=0.1, grad_clip=0.0)
    params = {"norm1": {"scale": jnp.ones((2, 4))},
              "mixer": {"wq": jnp.ones((2, 4, 4))},
              "ffn": {"route_bias": jnp.ones((2, 8))}}
    zero = jax.tree.map(jnp.zeros_like, params)
    new, _, _ = adam.update(params, zero, adam.init(params), tcfg)
    assert float(jnp.max(new["mixer"]["wq"])) < 1.0
    assert float(jnp.min(new["norm1"]["scale"])) == 1.0
    assert float(jnp.min(new["ffn"]["route_bias"])) == 1.0


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------
def test_ckpt_roundtrip_nested():
    tree = {"a": {"b": jnp.arange(6).reshape(2, 3).astype(jnp.bfloat16)},
            "c": [jnp.ones(4), jnp.zeros((2, 2), jnp.int32)]}
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "x.npz")
        ckpt.save(p, tree)
        back = ckpt.restore(p, tree)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_ckpt_shape_mismatch_raises():
    tree = {"a": jnp.ones(3)}
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "x.npz")
        ckpt.save(p, tree)
        with pytest.raises(ValueError):
            ckpt.restore(p, {"a": jnp.ones(4)})


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------
class _FakeMesh:
    def __init__(self, **axes):
        self.shape = dict(axes)
        self.axis_names = tuple(axes)


MESH = _FakeMesh(data=16, model=16)
MESH3 = _FakeMesh(pod=2, data=16, model=16)


@given(st.lists(st.sampled_from([1, 2, 3, 5, 8, 16, 24, 40, 128, 256_000]),
                min_size=1, max_size=4))
@settings(max_examples=60, deadline=None)
def test_legalize_always_divides(dims):
    spec = P(*( ["model"] + [None] * (len(dims) - 1)))
    out = rules.legalize(spec, tuple(dims), MESH)
    for d, entry in enumerate(out):
        if entry is not None:
            assert dims[d] % rules._axis_size(MESH, entry) == 0


@pytest.mark.parametrize("arch", ASSIGNED)
@pytest.mark.parametrize("mesh", [MESH, MESH3])
def test_param_specs_legal_all_archs(arch, mesh):
    from repro.launch import specs as sp
    cfg = get_config(arch)
    pspec = sp.param_specs(cfg)
    specs = rules.param_specs(pspec, mesh)
    flat = jax.tree_util.tree_flatten_with_path(pspec)[0]
    spec_flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat) == len(spec_flat)
    n_model_sharded = 0
    for (path, leaf), spec in zip(flat, spec_flat):
        for d, entry in enumerate(spec):
            if entry is None:
                continue
            assert leaf.shape[d] % rules._axis_size(mesh, entry) == 0, (
                arch, path, leaf.shape, spec)
            n_model_sharded += 1
    assert n_model_sharded > 0, arch  # something actually shards


def test_moe_experts_shard_over_model():
    from repro.launch import specs as sp
    cfg = get_config("granite-moe-1b-a400m")
    pspec = sp.param_specs(cfg)
    specs = rules.param_specs(pspec, MESH)
    s = specs["blocks"]["pos0"]["ffn"]["wi"]
    assert s[1] == "model"  # (stack, E, d, f): experts dim sharded


def test_cache_auto_policy():
    """§Perf-measured policy: split-KV (seq-sharded cache) for GQA archs
    (gemma2); head-sharding for MHA (qwen1.5-32b)."""
    from repro.configs import INPUT_SHAPES
    from repro.launch import specs as sp

    def kv_spec(arch):
        cfg = get_config(arch)
        st_ = sp.decode_state_specs(cfg, INPUT_SHAPES["decode_32k"])
        specs = rules.cache_specs(st_, MESH, strategy="auto", cfg=cfg)
        key = "pos0" if "pos0" in specs else "rem0"
        layer = specs[key]
        while "k" not in layer:  # nested pattern positions
            layer = next(iter(layer.values()))
        return layer["k"]

    gem = kv_spec("gemma2-9b")      # GQA (kv=8 < 16 heads) -> seq sharded
    assert gem[2] == "model"
    assert len(gem) <= 3 or gem[3] is None  # kv-head dim unsharded
    qw = kv_spec("qwen1.5-32b")     # MHA -> head/hd sharding retained
    assert len(qw) <= 2 or qw[2] != "model"
    # recurrent-state archs unaffected by the policy
    xl = rules.cache_specs(
        sp.decode_state_specs(get_config("xlstm-125m"),
                              INPUT_SHAPES["decode_32k"]),
        MESH, strategy="auto", cfg=get_config("xlstm-125m"))
    assert xl


def test_cache_specs_legal():
    from repro.configs import INPUT_SHAPES
    from repro.launch import specs as sp
    for arch in ("qwen1.5-32b", "recurrentgemma-2b", "xlstm-125m"):
        cfg = get_config(arch)
        st_ = sp.decode_state_specs(cfg, INPUT_SHAPES["decode_32k"])
        specs = rules.cache_specs(st_, MESH)
        flat = jax.tree_util.tree_flatten_with_path(st_)[0]
        spec_flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        for (path, leaf), spec in zip(flat, spec_flat):
            for d, entry in enumerate(spec):
                if entry is not None:
                    assert leaf.shape[d] % rules._axis_size(MESH, entry) == 0, (
                        arch, path, leaf.shape, spec)
