"""Plain float32 reference of the benchmark's decoder models, and the
benchmark's own weights, for any architecture of ``bench/models/``.

Nothing here imports the program. The weights are made from the seed by
``init_params`` in the architecture module's own layout (layers stacked
on a leading axis); an entry re-keys them into the program's layout, and
the reference makes its own copy from the same seed. The module gives the
layers, the head's loss and the leaves Adam decays; this file trains them
layer by layer (``LayerwiseTrainer``) and reads what ``bench/check.py``
compares.

Every matrix product runs at ``Precision.HIGHEST``. ``quant`` replaces the
operands of every product by their value rounded to a lower precision
(the control of ``bench/control.py``); the gradient passes straight
through the rounding.
"""
from __future__ import annotations

import itertools
import math
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int):
    """A PRNG key that uses all of ``seed``: JAX's ``PRNGKey`` keeps only
    its low 32 bits, and the benchmark's seeds go past 2**32."""
    words = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


# ---------------------------------------------------------------------------
# Weights and the layers' stacks
# ---------------------------------------------------------------------------
def init_params(arch, m: Dict, key) -> Dict:
    """Seeded float32 weights in ``arch``'s layout. Weights
    ~ N(0, 1/fan_in); biases ~ N(0, 0.02^2); norm scales
    ~ 1 + N(0, 0.02^2). Leaf i of the flattened shapes draws from
    ``fold_in(key, i)``."""
    shapes = arch.param_shapes(m)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = _name(path)
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shape, jnp.float32)
        fan = arch.fan_in(name, shape)
        if fan is not None:
            leaves.append(z / math.sqrt(fan))
        elif name.endswith("scale"):
            leaves.append(1.0 + 0.02 * z)
        else:
            leaves.append(0.02 * z)
    return jax.tree.unflatten(tree, leaves)


def _name(path) -> str:
    """The last key of a leaf's path: ``wq``, ``scale``, ``embed``."""
    return jax.tree_util.keystr(path[-1:]).strip("[]'")


def layer_rows(kinds: Sequence[str]) -> Dict[str, List[int]]:
    """The layer index of each row of each kind's stack."""
    rows: Dict[str, List[int]] = {}
    for i, kind in enumerate(kinds):
        rows.setdefault(kind, []).append(i)
    return rows


def split_layers(params: Dict, kinds: Sequence[str]):
    """``(head, [layer 0's leaves, layer 1's, ...])``: the head leaves, and
    each layer's row of its kind's stack (``layers`` itself where every
    layer is of one kind, ``layers[kind]`` otherwise)."""
    head = {k: v for k, v in params.items() if k != "layers"}
    rows = layer_rows(kinds)
    stacks = params["layers"] if len(rows) > 1 else {kinds[0]: params["layers"]}
    layers = [None] * len(kinds)
    for kind, idx in rows.items():
        for j, i in enumerate(idx):
            layers[i] = jax.tree.map(lambda a: a[j], stacks[kind])
    return head, layers


def leaf_norms(tree: Dict) -> Dict[str, jnp.ndarray]:
    """L2 norm of every leaf, a stacked layer leaf split per row:
    ``{"embed": (), "layers.wq": (L,), ...}``."""
    out = {}
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = ".".join(jax.tree_util.keystr((p,)).strip("[]'") for p in path)
        a = a.astype(jnp.float32)
        if name.startswith("layers."):
            out[name] = jnp.sqrt(jnp.sum(jnp.square(a), axis=tuple(
                range(1, a.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(a)))
    return out


def per_leaf(norms: Dict, kinds: Sequence[str]) -> Dict[str, float]:
    """Host floats of ``leaf_norms``, per layer: ``layers.3.wq``, with 3
    the layer's index in the model whatever its kind."""
    rows = layer_rows(kinds)
    out = {}
    for name, v in norms.items():
        v = np.asarray(v, np.float64)
        if not name.startswith("layers."):
            out[name] = float(v)
            continue
        rest = name[len("layers."):]
        kind, leaf = rest.split(".", 1) if len(rows) > 1 else (kinds[0], rest)
        for i, x in zip(rows[kind], v):
            out[f"layers.{i}.{leaf}"] = float(x)
    return out


# ---------------------------------------------------------------------------
# Products and the whole model's loss
# ---------------------------------------------------------------------------
def fp8_round(x):
    """Per-tensor scaled float8 (e4m3) rounding, gradient straight
    through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    r = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(r - x)


QUANT = {"fp8": fp8_round}


def mm(eq, a, b, quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def loss(arch, params, tokens, labels, m: Dict,
         quant: Optional[Callable] = None):
    """The rows' loss, the whole model at once: the head's mean
    cross-entropy plus every layer's ``aux``. Each run of consecutive
    layers of one kind is one ``lax.scan``."""
    kinds = arch.layer_kinds(m)
    head, layers = split_layers(params, kinds)
    carry = (arch.embed(head, tokens), jnp.zeros((), jnp.float32))
    i = 0
    for kind, run in itertools.groupby(kinds):
        n = len(list(run))

        def body(c, lp, kind=kind):
            x, a = arch.layer(m, kind, quant, lp, c[0])
            return (x, c[1] + sum(jax.tree.leaves(a))), None
        carry, _ = jax.lax.scan(body, carry, jax.tree.map(
            lambda *a: jnp.stack(a), *layers[i:i + n]))
        i += n
    x, aux = carry
    return arch.head_loss(m, quant, head, x, labels) + aux


# ---------------------------------------------------------------------------
# Adam, as the job's optimizer block states it
# ---------------------------------------------------------------------------
def lr_at(opt: Dict, step: int) -> float:
    """Learning rate of the update that follows ``step`` earlier ones:
    linear warm-up, then cosine down to a tenth."""
    warm = min(1.0, (step + 1) / max(opt["warmup_steps"], 1))
    prog = min(max((step - opt["warmup_steps"])
                   / max(opt["steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    return opt["learning_rate"] * warm * (0.1 + 0.9 * 0.5 * (
        1.0 + math.cos(math.pi * prog)))


# ---------------------------------------------------------------------------
# The readings that decide ``correct``
# ---------------------------------------------------------------------------
class LayerwiseTrainer:
    """Trains the reference one layer at a time: layer i's weights, Adam
    state and gradients live on ``devices[i * len(devices) // L]``, the
    embedding, final norm and head on ``devices[0]``. Forward keeps each
    layer's input; backward recomputes one layer at a time from it, with
    one forward and one backward jitted per kind of layer. So the reference
    of a model that fills several chips fits beside nothing else, and a
    model on one chip trains with its memory in blocks.

    Each row is trained as a micro-batch of its own: a layer's ``aux``
    enters the loss of the row it was computed over."""

    def __init__(self, arch, m: Dict, opt: Dict, key, devices, quant=None,
                 decays: Optional[Callable[[str], bool]] = None):
        self.m, self.opt = m, opt
        decays = decays or arch.DECAYED.__contains__
        q = QUANT[quant] if quant else None
        self.kinds = tuple(arch.layer_kinds(m))
        L = len(self.kinds)
        self.devs = [devices[i * len(devices) // L] for i in range(L)]
        self.head_dev = devices[0]
        self.init = jax.jit(lambda k: init_params(arch, m, k))

        def head_loss(head, x, labels):
            return arch.head_loss(m, q, head, x, labels)

        def bwd(layer):
            def f(lp, x, g):
                (_, aux), vjp_fn = jax.vjp(layer, lp, x)
                return vjp_fn((g, jax.tree.map(jnp.ones_like, aux)))
            return jax.jit(f)

        layers = {k: (lambda lp, x, k=k: arch.layer(m, k, q, lp, x))
                  for k in set(self.kinds)}
        self.embed = jax.jit(arch.embed)
        self.layer_fwd = {k: jax.jit(f) for k, f in layers.items()}
        self.layer_bwd = {k: bwd(f) for k, f in layers.items()}
        self.head_vg = jax.jit(jax.value_and_grad(head_loss, argnums=(0, 1)))
        self.embed_bwd = jax.jit(lambda head, t, g: jax.vjp(
            lambda h: arch.embed(h, t), head)[1](g)[0])
        self.add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                           donate_argnums=0)
        self.sq = jax.jit(lambda t: sum(jnp.sum(jnp.square(a))
                                        for a in jax.tree.leaves(t)))
        self.norms = jax.jit(leaf_norms)

        def update(p, g, a, n, scale, lr, bc1, bc2):
            b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
            g = jax.tree.map(lambda x: x * scale, g)
            a = jax.tree.map(lambda x, y: b1 * x + (1 - b1) * y, a, g)
            n = jax.tree.map(lambda x, y: b2 * x + (1 - b2) * y * y, n, g)

            def upd(path, p_, a_, n_):
                d = (a_ / bc1) / (jnp.sqrt(n_ / bc2) + eps)
                if opt["weight_decay"] and decays(_name(path)):
                    d = d + opt["weight_decay"] * p_
                return p_ - lr * d
            return (jax.tree_util.tree_map_with_path(upd, p, a, n), g, a,
                    n)

        self.update = jax.jit(update, donate_argnums=(0, 1, 2, 3))
        self.key = key

    def _split(self, params):
        head, layers = split_layers(params, self.kinds)
        head = jax.device_put(head, self.head_dev)
        layers = [jax.device_put(lp, d) for lp, d in zip(layers, self.devs)]
        return head, layers

    def _grads(self, head, layers, tokens, labels):
        """Loss and gradients of one block of rows."""
        put = jax.device_put
        tokens = put(tokens, self.head_dev)
        x, xs, auxes = self.embed(head, tokens), [], []
        for kind, lp, d in zip(self.kinds, layers, self.devs):
            xs.append(put(x, d))            # each layer's input, kept
            x, aux = self.layer_fwd[kind](lp, xs[-1])
            auxes += jax.tree.leaves(aux)
        loss, (g_head, g) = self.head_vg(head, put(x, self.head_dev),
                                         put(labels, self.head_dev))
        g_layers = [None] * len(layers)
        for i in reversed(range(len(layers))):
            g_layers[i], g = self.layer_bwd[self.kinds[i]](
                layers[i], xs[i], put(g, self.devs[i]))
        g_head = self.add(g_head, self.embed_bwd(head, tokens,
                                                 put(g, self.head_dev)))
        return float(loss) + sum(float(a) for a in auxes), g_head, g_layers

    def readings(self, batches) -> Dict:
        """Train one update per batch, a row at a time, and return what
        ``bench/check.py`` compares: ``loss`` (each step's mean loss),
        ``grad`` (per-leaf norm of the first step's clipped gradient) and
        ``change`` (per-leaf norm of the weights' change after the last
        update)."""
        opt = self.opt
        head, layers = self._split(self.init(self.key))
        groups = [head] + layers
        mu = [jax.tree.map(jnp.zeros_like, g) for g in groups]
        nu = [jax.tree.map(jnp.zeros_like, g) for g in groups]
        losses, first = [], None
        for step, batch in enumerate(batches):
            rows = batch["tokens"].shape[0]
            acc, total = None, 0.0
            for r in range(rows):
                lv, gh, gl = self._grads(head, layers,
                                         batch["tokens"][r:r + 1],
                                         batch["labels"][r:r + 1])
                grads = [gh] + gl
                acc = grads if acc is None else [
                    self.add(a, g) for a, g in zip(acc, grads)]
                total += lv
            losses.append(total / rows)
            gn = math.sqrt(sum(float(self.sq(g)) for g in acc)) / rows
            scale = min(1.0, opt["grad_clip"] / (gn + 1e-9)) / rows
            lr = lr_at(opt, step)
            bc1, bc2 = 1 - opt["b1"] ** (step + 1), 1 - opt["b2"] ** (step + 1)
            out = [self.update(p, g, a, n, jnp.float32(scale),
                               jnp.float32(lr), jnp.float32(bc1),
                               jnp.float32(bc2))
                   for p, g, a, n in zip(groups, acc, mu, nu)]
            groups = [o[0] for o in out]
            mu, nu = [o[2] for o in out], [o[3] for o in out]
            if first is None:
                first = self._per_leaf([o[1] for o in out])
            del out, acc
            head, layers = groups[0], groups[1:]
        del mu, nu
        p0 = self._split(self.init(self.key))
        diff = [jax.tree.map(jnp.subtract, a, b)
                for a, b in zip(groups, [p0[0]] + p0[1])]
        return {"loss": losses, "grad": first, "change": self._per_leaf(diff)}

    def _per_leaf(self, groups) -> Dict[str, float]:
        out = per_leaf(self.norms(groups[0]), self.kinds)
        for i, g in enumerate(groups[1:]):
            for k, v in self.norms(g).items():
                out[f"layers.{i}.{k}"] = float(v)
        return out


def train_readings(arch, m: Dict, opt: Dict, batches, key,
                   quant: Optional[str] = None, devices=None, **kw) -> Dict:
    """The reference's readings of ``len(batches)`` steps from the seed's
    weights (see ``LayerwiseTrainer.readings``)."""
    return LayerwiseTrainer(arch, m, opt, key, devices or jax.devices()[:1],
                            quant, **kw).readings(batches)
