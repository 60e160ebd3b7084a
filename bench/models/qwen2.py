"""Reference of the Qwen1.5 decoder (Hugging Face's Qwen2 architecture) as
the configuration file's ``model`` block sizes it: pre-norm layers with
RMSNorm, multi-head or grouped attention with optional q/k/v bias and
rotate-half rotary positions, a SwiGLU MLP, unscaled token embeddings, a
tied or separate output head, and next-token cross-entropy averaged over
every token. Adam decays the weight matrices (``DECAYED``) and no norm
scale or bias. Every layer is of one kind, so ``layers`` is one stack.

The interface is ``bench/models/__init__.py``'s; nothing here imports the
program. ``to_program`` and ``from_program`` re-key the weights into
``models.model``'s layout, one scanned block ``blocks.pos0``.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import mm

NORM_EPS = 1e-6
ATTN = "attn"
#: the leaves weight decay applies to: every weight matrix, no norm or bias
DECAYED = frozenset({"embed", "unembed", "wq", "wk", "wv", "wo", "wi", "wg",
                     "w2"})


def layer_kinds(m: Dict):
    return (ATTN,) * m["num_layers"]


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


def fan_in(name: str, shape) -> Optional[int]:
    """Fan-in of a weight (its init scale is 1/sqrt(fan_in)); None for
    norms and biases. Layer leaves carry the leading layer axis."""
    if name in ("embed", "unembed"):
        return shape[-1] if name == "embed" else shape[0]
    if name in ("wq", "wk", "wv", "wi", "wg"):
        return shape[1]
    if name == "wo":
        return shape[1] * shape[2]
    if name == "w2":
        return shape[1]
    return None


# ---------------------------------------------------------------------------
# Forward and loss
# ---------------------------------------------------------------------------
def rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + NORM_EPS) * scale


def rope(x, theta):
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.arange(s, dtype=np.float64)[:, None] * freq[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(m, quant, lp, h, window: int = 0):
    """Causal self-attention of the normed input ``h``: each position sees
    itself and the ``window - 1`` before it, or every earlier one where
    ``window`` is 0."""
    b, s, _ = h.shape
    nq, nkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    q = mm("bsd,dnh->bsnh", h, lp["wq"], quant)
    k = mm("bsd,dnh->bsnh", h, lp["wk"], quant)
    v = mm("bsd,dnh->bsnh", h, lp["wv"], quant)
    if "bq" in lp:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q, k = rope(q, m["rope_theta"]), rope(k, m["rope_theta"])
    g = nq // nkv
    q = q.reshape(b, s, nkv, g, hd)
    sc = mm("bqngh,bknh->bngqk", q, k, quant) / math.sqrt(hd)
    causal = np.tril(np.ones((s, s), bool))
    if window:
        causal &= np.triu(np.ones((s, s), bool), 1 - window)
    sc = jnp.where(causal, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = mm("bngqk,bknh->bqngh", p, v, quant).reshape(b, s, nq, hd)
    return mm("bsnh,nhd->bsd", o, lp["wo"], quant)


def swiglu(quant, h, wi, wg, w2):
    u = jax.nn.silu(mm("bsd,df->bsf", h, wi, quant)) \
        * mm("bsd,df->bsf", h, wg, quant)
    return mm("bsf,fd->bsd", u, w2, quant)


def layer(m, kind, quant, lp, x):
    x = x + attention(m, quant, lp, rms_norm(x, lp["ln1_scale"]))
    h = rms_norm(x, lp["ln2_scale"])
    return x + swiglu(quant, h, lp["wi"], lp["wg"], lp["w2"]), None


def embed(head, tokens):
    return head["embed"][tokens]


def head_loss(m, quant, head, x, labels):
    """Mean next-token cross-entropy over every token of the rows."""
    x = rms_norm(x, head["final_norm"]["scale"])
    if "unembed" in head:
        logits = mm("bsd,dv->bsv", x, head["unembed"], quant)
    else:
        logits = mm("bsd,vd->bsv", x, head["embed"], quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)


# ---------------------------------------------------------------------------
# The program's layout
# ---------------------------------------------------------------------------
#: bench layer leaf -> (program sub-tree, key) inside one stacked layer
LAYER_KEYS = {"ln1_scale": ("norm1", "scale"), "ln2_scale": ("norm2", "scale"),
              "wq": ("mixer", "wq"), "wk": ("mixer", "wk"),
              "wv": ("mixer", "wv"), "wo": ("mixer", "wo"),
              "bq": ("mixer", "bq"), "bk": ("mixer", "bk"),
              "bv": ("mixer", "bv"), "wi": ("ffn", "wi"),
              "wg": ("ffn", "wg"), "w2": ("ffn", "wo")}


def _check(cfg):
    if cfg.block_pattern != (ATTN,) or cfg.moe is not None:
        raise ValueError(f"qwen2 reference: {cfg.name} is not a uniform "
                         "attention decoder with a dense MLP")


def to_program(t, cfg):
    """The benchmark's layout -> ``models.model``'s (a re-keying)."""
    _check(cfg)
    layer = {}
    for k, a in t["layers"].items():
        sub, key = LAYER_KEYS[k]
        layer.setdefault(sub, {})[key] = a
    embed = {"table": t["embed"]}
    if "unembed" in t:
        embed["unembed"] = t["unembed"]
    return {"embed": embed, "blocks": {"pos0": layer},
            "final_norm": t["final_norm"]}


def from_program(p, cfg):
    """``models.model``'s layout -> the benchmark's."""
    _check(cfg)
    layer = {k: p["blocks"]["pos0"][sub][key]
             for k, (sub, key) in LAYER_KEYS.items()
             if key in p["blocks"]["pos0"].get(sub, {})}
    out = {"embed": p["embed"]["table"], "layers": layer,
           "final_norm": p["final_norm"]}
    if "unembed" in p["embed"]:
        out["unembed"] = p["embed"]["unembed"]
    return out


# ---------------------------------------------------------------------------
# Work
# ---------------------------------------------------------------------------
def model_flops_train(m: Dict, b: int, s: int) -> float:
    """Matmul FLOPs of one training step over b rows of s tokens: forward
    + backward = 3 x forward, causal attention counted as half the square,
    the logits once, recomputation left out."""
    d, hd, nq, nkv = m["d_model"], m["head_dim"], m["num_heads"], \
        m["num_kv_heads"]
    per_layer = (2 * b * s * d * hd * (nq + 2 * nkv)       # qkv projections
                 + 2 * b * s * nq * hd * d                 # output projection
                 + 2 * 2 * b * nq * s * s * hd * 0.5       # qk^T and pv
                 + 2 * b * s * 3 * d * m["d_ff"])          # SwiGLU MLP
    logits = 2 * b * s * d * m["vocab_size"]
    return 3.0 * (m["num_layers"] * per_layer + logits)
