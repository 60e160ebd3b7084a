"""Plain float32 reference of the benchmark's decoder models, and the
benchmark's own weights.

Nothing here imports the program. The weights are made from the seed by
``init_params`` in this module's own layout (layers stacked on a leading
axis); an entry re-keys them into the program's layout, and the reference
makes its own copy from the same seed. The model is the published Qwen1.5
(Hugging Face's Qwen2) decoder as the configuration file's ``model`` block
sizes it: pre-norm layers with RMSNorm, multi-head or grouped attention
with optional q/k/v bias and rotate-half rotary positions, a SwiGLU MLP,
unscaled token embeddings, a tied or separate output head, and next-token
cross-entropy averaged over every token. Adam decays the weight matrices
(``DECAYED``) and no norm scale or bias.

Every matrix product runs at ``Precision.HIGHEST``. ``quant`` replaces the
operands of every product by their value rounded to a lower precision
(the control of ``bench/control.py``); the gradient passes straight
through the rounding.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NORM_EPS = 1e-6
#: the leaves weight decay applies to: every weight matrix, no norm or bias
DECAYED = frozenset({"embed", "unembed", "wq", "wk", "wv", "wo", "wi", "wg",
                     "w2"})


def seed_key(seed: int):
    """A PRNG key that uses all of ``seed``: JAX's ``PRNGKey`` keeps only
    its low 32 bits, and the benchmark's seeds go past 2**32."""
    words = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------
def param_shapes(m: Dict) -> Dict:
    """Leaf shapes of the layout, from the configuration's ``model``."""
    L, D, F, V = m["num_layers"], m["d_model"], m["d_ff"], m["vocab_size"]
    nq, nkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    lay = {"ln1_scale": (L, D), "wq": (L, D, nq, hd), "wk": (L, D, nkv, hd),
           "wv": (L, D, nkv, hd), "wo": (L, nq, hd, D),
           "ln2_scale": (L, D), "wi": (L, D, F), "wg": (L, D, F),
           "w2": (L, F, D)}
    if m["qkv_bias"]:
        lay.update(bq=(L, nq, hd), bk=(L, nkv, hd), bv=(L, nkv, hd))
    out = {"embed": (V, D), "final_norm": {"scale": (D,)}, "layers": lay}
    if not m["tie_embeddings"]:
        out["unembed"] = (D, V)
    return out


def _fan_in(name: str, shape) -> Optional[int]:
    """Fan-in of a weight (its init scale is 1/sqrt(fan_in)); None for
    norms and biases."""
    if name in ("embed", "unembed"):
        return shape[-1] if name == "embed" else shape[0]
    if name in ("wq", "wk", "wv", "wi", "wg"):
        return shape[1]
    if name == "wo":
        return shape[1] * shape[2]
    if name == "w2":
        return shape[1]
    return None


def init_params(m: Dict, key) -> Dict:
    """Seeded float32 weights. Weights ~ N(0, 1/fan_in); biases
    ~ N(0, 0.02^2); norm scales ~ 1 + N(0, 0.02^2)."""
    shapes = param_shapes(m)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = _name(path)
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shape, jnp.float32)
        fan = _fan_in(name, shape)
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


def leaf_norms(tree: Dict) -> Dict[str, jnp.ndarray]:
    """L2 norm of every leaf, a stacked layer leaf split per layer:
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


def per_leaf(norms: Dict) -> Dict[str, float]:
    """Host floats of ``leaf_norms``, per layer: ``layers.3.wq``."""
    out = {}
    for name, v in norms.items():
        v = np.asarray(v, np.float64)
        if name.startswith("layers."):
            for i, x in enumerate(v):
                out[f"layers.{i}.{name[len('layers.'):]}"] = float(x)
        else:
            out[name] = float(v)
    return out


# ---------------------------------------------------------------------------
# Forward and loss
# ---------------------------------------------------------------------------
def fp8_round(x):
    """Per-tensor scaled float8 (e4m3) rounding, gradient straight
    through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    r = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(r - x)


QUANT = {"fp8": fp8_round}


def _mm(eq, a, b, quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + NORM_EPS) * scale


def _rope(x, theta):
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.arange(s, dtype=np.float64)[:, None] * freq[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(m, quant, x, lp):
    b, s, _ = x.shape
    nq, nkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    h = _norm(x, lp["ln1_scale"])
    q = _mm("bsd,dnh->bsnh", h, lp["wq"], quant)
    k = _mm("bsd,dnh->bsnh", h, lp["wk"], quant)
    v = _mm("bsd,dnh->bsnh", h, lp["wv"], quant)
    if "bq" in lp:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    g = nq // nkv
    q = q.reshape(b, s, nkv, g, hd)
    sc = _mm("bqngh,bknh->bngqk", q, k, quant) / math.sqrt(hd)
    causal = np.tril(np.ones((s, s), bool))
    sc = jnp.where(causal, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = _mm("bngqk,bknh->bqngh", p, v, quant).reshape(b, s, nq, hd)
    x = x + _mm("bsnh,nhd->bsd", o, lp["wo"], quant)
    h = _norm(x, lp["ln2_scale"])
    u = jax.nn.silu(_mm("bsd,df->bsf", h, lp["wi"], quant)) \
        * _mm("bsd,df->bsf", h, lp["wg"], quant)
    return x + _mm("bsf,fd->bsd", u, lp["w2"], quant), None


def loss(params, tokens, labels, m: Dict, quant: Optional[Callable] = None):
    """Mean next-token cross-entropy over every token of the rows."""
    x = params["embed"][tokens]
    x, _ = jax.lax.scan(lambda c, lp: _layer(m, quant, c, lp), x,
                        params["layers"])
    fn = params["final_norm"]
    x = _norm(x, fn["scale"])
    if "unembed" in params:
        logits = _mm("bsd,dv->bsv", x, params["unembed"], quant)
    else:
        logits = _mm("bsd,vd->bsv", x, params["embed"], quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)


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
    layer's input; backward recomputes one layer at a time from it. So the
    reference of a model that fills several chips fits beside nothing
    else, and a model on one chip trains with its memory in blocks."""

    def __init__(self, m: Dict, opt: Dict, key, devices, quant=None,
                 decays: Callable[[str], bool] = DECAYED.__contains__):
        self.m, self.opt = m, opt
        q = QUANT[quant] if quant else None
        L = m["num_layers"]
        self.devs = [devices[i * len(devices) // L] for i in range(L)]
        self.head_dev = devices[0]
        self.init = jax.jit(lambda k: init_params(m, k))

        def head_loss(head, x, labels):
            fn = head["final_norm"]
            x = _norm(x, fn["scale"])
            if "unembed" in head:
                logits = _mm("bsd,dv->bsv", x, head["unembed"], q)
            else:
                logits = _mm("bsd,vd->bsv", x, head["embed"], q)
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
            return jnp.mean(lse - picked)

        def embed(head, tokens):
            return head["embed"][tokens]

        def layer(lp, x):
            return _layer(m, q, x, lp)[0]

        self.embed = jax.jit(embed)
        self.layer_fwd = jax.jit(layer)
        self.layer_bwd = jax.jit(lambda lp, x, g: jax.vjp(layer, lp, x)[1](g))
        self.head_vg = jax.jit(jax.value_and_grad(head_loss, argnums=(0, 1)))
        self.embed_bwd = jax.jit(lambda head, t, g: jax.vjp(
            lambda h: embed(h, t), head)[1](g)[0])
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
        head = {k: v for k, v in params.items() if k != "layers"}
        head = jax.device_put(head, self.head_dev)
        layers = [jax.device_put(jax.tree.map(lambda a: a[i],
                                              params["layers"]), d)
                  for i, d in enumerate(self.devs)]
        return head, layers

    def _grads(self, head, layers, tokens, labels):
        """Loss and gradients of one block of rows."""
        put = jax.device_put
        tokens = put(tokens, self.head_dev)
        x, xs = self.embed(head, tokens), []
        for lp, d in zip(layers, self.devs):
            xs.append(put(x, d))            # each layer's input, kept
            x = self.layer_fwd(lp, xs[-1])
        loss, (g_head, g) = self.head_vg(head, put(x, self.head_dev),
                                         put(labels, self.head_dev))
        g_layers = [None] * len(layers)
        for i in reversed(range(len(layers))):
            g_layers[i], g = self.layer_bwd(layers[i], xs[i],
                                            put(g, self.devs[i]))
        g_head = self.add(g_head, self.embed_bwd(head, tokens,
                                                 put(g, self.head_dev)))
        return loss, g_head, g_layers

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
                total += float(lv)
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
        out = per_leaf(self.norms(groups[0]))
        for i, g in enumerate(groups[1:]):
            for k, v in self.norms(g).items():
                out[f"layers.{i}.{k}"] = float(v)
        return out


def train_readings(m: Dict, opt: Dict, batches, key, quant: Optional[str] = None,
                   devices=None, **kw) -> Dict:
    """The reference's readings of ``len(batches)`` steps from the seed's
    weights (see ``LayerwiseTrainer.readings``)."""
    return LayerwiseTrainer(m, opt, key, devices or jax.devices()[:1],
                            quant, **kw).readings(batches)
