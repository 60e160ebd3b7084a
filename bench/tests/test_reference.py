"""The reference's layer-by-layer backward equals autodiff of its whole
model (``reference.loss``), and its Adam decays the weight matrices
only."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from tiny import TINY_MODEL, tiny_cell


@pytest.mark.parametrize("tied", [True, False])
def test_layerwise_grads_equal_whole_model(tied):
    cell = tiny_cell(model={"tie_embeddings": tied, "num_layers": 2})
    m = cell.config["model"]
    key = reference.seed_key(2**40 + 1)
    tr = reference.LayerwiseTrainer(m, cell.job["optimizer"], key,
                                    jax.devices()[:1])
    params = tr.init(key)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, TINY_MODEL["vocab_size"], (1, 33)).astype(np.int32)
    t, lab = toks[:, :-1], toks[:, 1:]
    head, layers = tr._split(params)
    lv, gh, gl = tr._grads(head, layers, t, lab)
    want_l, want = jax.value_and_grad(reference.loss)(
        params, jnp.asarray(t), jnp.asarray(lab), m)
    assert float(lv) == pytest.approx(float(want_l), rel=1e-6)
    for k, g in gh.items():
        np.testing.assert_allclose(jax.tree.leaves(g), jax.tree.leaves(want[k]),
                                   rtol=2e-5, atol=1e-7)
    for i, g in enumerate(gl):
        for k, a in g.items():
            np.testing.assert_allclose(a, want["layers"][k][i], rtol=2e-5,
                                       atol=1e-7)


def test_decay_leaves_norms_and_biases():
    cell = tiny_cell(model={"num_layers": 2})
    m, opt = cell.config["model"], cell.job["optimizer"]
    key = reference.seed_key(5)
    tr = reference.LayerwiseTrainer(m, opt, key, jax.devices()[:1])
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
        want = a * (1 - wd) if name in reference.DECAYED else a
        np.testing.assert_allclose(got[path], want, rtol=1e-6, err_msg=name)
