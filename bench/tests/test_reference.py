"""For each architecture module, the reference's layer-by-layer backward
equals autodiff of its whole model (``reference.loss``), and its Adam
decays the module's weight matrices only: ``bench/models/qwen2.py``, one
kind of layer, and the test-only ``moe_ref``, two kinds with an aux
loss."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from tiny import tiny_cell, tiny_moe_cell

#: each module's tiny cell, with as many layers as it needs for two rows
#: of a stack (``moe_ref``: two of each kind)
CELLS = {"qwen2": lambda: _sized(tiny_cell(), 2),
         "moe_ref": lambda: _sized(tiny_moe_cell(), 4)}


#: absolute tolerance of a gradient element: float32 round-off of the
#: whole-model and the layer-by-layer programs, which sum in different
#: orders; the experts' gradients sum over every token and read up to
#: 2e-7 apart on elements of 1e-3
ATOL = {"qwen2": 1e-7, "moe_ref": 1e-6}


def _sized(cell, layers):
    cell.config["model"]["num_layers"] = layers
    return cell


@pytest.mark.parametrize("tied", [True, False])
@pytest.mark.parametrize("arch", sorted(CELLS))
def test_layerwise_grads_equal_whole_model(arch, tied):
    cell = CELLS[arch]()
    m = cell.config["model"]
    m["tie_embeddings"] = tied
    key = reference.seed_key(2**40 + 1)
    tr = reference.LayerwiseTrainer(cell.arch, m, cell.job["optimizer"], key,
                                    jax.devices()[:1])
    params = tr.init(key)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, m["vocab_size"], (1, 33)).astype(np.int32)
    t, lab = toks[:, :-1], toks[:, 1:]
    head, layers = tr._split(params)
    lv, gh, gl = tr._grads(head, layers, t, lab)
    want_l, want = jax.value_and_grad(reference.loss, argnums=1)(
        cell.arch, params, jnp.asarray(t), jnp.asarray(lab), m)
    assert lv == pytest.approx(float(want_l), rel=1e-6)
    want_head, want_layers = reference.split_layers(want, tr.kinds)
    for k, g in gh.items():
        np.testing.assert_allclose(jax.tree.leaves(g),
                                   jax.tree.leaves(want_head[k]),
                                   rtol=2e-5, atol=ATOL[arch])
    for g, w in zip(gl, want_layers):
        assert set(g) == set(w)
        for k, a in g.items():
            np.testing.assert_allclose(a, w[k], rtol=2e-5, atol=ATOL[arch])


@pytest.mark.parametrize("arch", sorted(CELLS))
def test_decay_leaves_norms_and_biases(arch):
    cell = CELLS[arch]()
    m, opt = cell.config["model"], cell.job["optimizer"]
    key = reference.seed_key(5)
    tr = reference.LayerwiseTrainer(cell.arch, m, opt, key, jax.devices()[:1])
    p = tr.init(key)
    like = lambda f: jax.tree.map(f, p)  # noqa: E731
    # zero gradient and moments: the update is the decay alone, at lr 1
    new = tr.update(like(jnp.copy), like(jnp.zeros_like),
                    like(jnp.zeros_like), like(jnp.ones_like),
                    *map(jnp.float32, (1.0, 1.0, 1.0, 1.0)))[0]
    wd = opt["weight_decay"]
    assert wd > 0
    got = dict(jax.tree_util.tree_flatten_with_path(new)[0])
    for path, a in jax.tree_util.tree_flatten_with_path(p)[0]:
        name = jax.tree_util.keystr(path[-1:]).strip("[]'")
        want = a * (1 - wd) if name in cell.arch.DECAYED else a
        np.testing.assert_allclose(got[path], want, rtol=1e-6, err_msg=name)
