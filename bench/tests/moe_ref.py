"""A test-only architecture module (``bench/models/__init__.py``'s
interface): the reference of the program's MoE block (``models/moe.py``)
under layers that alternate sliding-window and global attention.

Each layer is pre-norm attention (``bench/models/qwen2.py``'s, windowed
to ``window_size`` positions on ``local_attn`` layers) followed by a
mixture of SwiGLU experts: a softmax router over ``num_experts``, the
``top_k`` largest probabilities as gates normalised to sum to 1, and every
expert computed densely on every token, weighted by its gate (0 where the
token did not pick it). Nothing is dropped, which the program matches with
a ``capacity_factor`` of ``num_experts / top_k``. The layer's ``aux`` is
the Switch load-balance loss times ``router_aux_weight``:
``num_experts * sum_e f_e * P_e`` over the row's tokens, with ``f_e`` the
share of (token, pick) pairs that chose expert e (so the shares sum to
``top_k``) and ``P_e`` its mean router probability.

The layers come in two kinds, so ``layers`` holds one stack per kind.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from bench.models import qwen2
from bench.reference import mm

LOCAL = "local_attn"
DECAYED = qwen2.DECAYED | {"router"}
embed = qwen2.embed
head_loss = qwen2.head_loss


def layer_kinds(m: Dict):
    pat, L = tuple(m["block_pattern"]), m["num_layers"]
    return (pat * L)[:L]


def param_shapes(m: Dict) -> Dict:
    D, V, e = m["d_model"], m["vocab_size"], m["moe"]
    nq, nkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    E, F = e["num_experts"], e["d_ff"]
    layers = {}
    for kind in dict.fromkeys(layer_kinds(m)):
        n = layer_kinds(m).count(kind)
        lay = {"ln1_scale": (n, D), "wq": (n, D, nq, hd),
               "wk": (n, D, nkv, hd), "wv": (n, D, nkv, hd),
               "wo": (n, nq, hd, D), "ln2_scale": (n, D),
               "router": (n, D, E), "wi": (n, E, D, F), "wg": (n, E, D, F),
               "w2": (n, E, F, D)}
        if m["qkv_bias"]:
            lay.update(bq=(n, nq, hd), bk=(n, nkv, hd), bv=(n, nkv, hd))
        layers[kind] = lay
    out = {"embed": (V, D), "final_norm": {"scale": (D,)}, "layers": layers}
    if not m["tie_embeddings"]:
        out["unembed"] = (D, V)
    return out


def fan_in(name: str, shape) -> Optional[int]:
    if name in ("wi", "wg", "w2"):
        return shape[2]             # (layer, expert, in, out)
    if name == "router":
        return shape[1]
    return qwen2.fan_in(name, shape)


def moe(m, quant, lp, h):
    """The experts' output for the normed input ``h``, and the aux loss."""
    e = m["moe"]
    E, k = e["num_experts"], e["top_k"]
    probs = jax.nn.softmax(mm("bsd,de->bse", h, lp["router"], quant), -1)
    top, idx = jax.lax.top_k(probs, k)
    picked = jax.nn.one_hot(idx, E, dtype=jnp.float32)         # (b, s, k, E)
    gate = jnp.einsum("bske,bsk->bse", picked,
                      top / jnp.sum(top, -1, keepdims=True))
    u = jax.nn.silu(mm("bsd,edf->bsef", h, lp["wi"], quant)) \
        * mm("bsd,edf->bsef", h, lp["wg"], quant)
    y = mm("bsef,efd->bsed", u, lp["w2"], quant)
    out = jnp.einsum("bsed,bse->bsd", y, gate,
                     precision=jax.lax.Precision.HIGHEST)
    f = jnp.mean(jnp.sum(picked, 2), axis=(0, 1))
    aux = E * jnp.sum(f * jnp.mean(probs, axis=(0, 1)))
    return out, aux * e["router_aux_weight"]


def layer(m, kind, quant, lp, x):
    window = m["window_size"] if kind == LOCAL else 0
    x = x + qwen2.attention(m, quant, lp, qwen2.rms_norm(x, lp["ln1_scale"]),
                            window)
    y, aux = moe(m, quant, lp, qwen2.rms_norm(x, lp["ln2_scale"]))
    return x + y, aux


#: bench layer leaf -> (program sub-tree, key) inside one stacked layer
LAYER_KEYS = {**{k: v for k, v in qwen2.LAYER_KEYS.items()
                 if k not in ("wi", "wg", "w2")},
              "router": ("ffn", "router"), "wi": ("ffn", "wi"),
              "wg": ("ffn", "wg"), "w2": ("ffn", "wo")}


def _pattern(cfg):
    pat = tuple(cfg.block_pattern)
    if (cfg.moe is None or len(set(pat)) != len(pat) or len(pat) < 2
            or cfg.num_layers % len(pat) or cfg.moe.shared_expert):
        raise ValueError(f"moe_ref: {cfg.name} is not a whole number of "
                         "blocks of distinct layer kinds with routed experts")
    return pat


def to_program(t, cfg):
    blocks = {}
    for j, kind in enumerate(_pattern(cfg)):
        layer = {}
        for k, a in t["layers"][kind].items():
            sub, key = LAYER_KEYS[k]
            layer.setdefault(sub, {})[key] = a
        blocks[f"pos{j}"] = layer
    embed_ = {"table": t["embed"]}
    if "unembed" in t:
        embed_["unembed"] = t["unembed"]
    return {"embed": embed_, "blocks": blocks, "final_norm": t["final_norm"]}


def from_program(p, cfg):
    layers = {}
    for j, kind in enumerate(_pattern(cfg)):
        blk = p["blocks"][f"pos{j}"]
        layers[kind] = {k: blk[sub][key] for k, (sub, key) in LAYER_KEYS.items()
                        if key in blk.get(sub, {})}
    out = {"embed": p["embed"]["table"], "layers": layers,
           "final_norm": p["final_norm"]}
    if "unembed" in p["embed"]:
        out["unembed"] = p["embed"]["unembed"]
    return out


def model_flops_train(m: Dict, b: int, s: int) -> float:
    """Matmul FLOPs of a training step (3 x forward): the routed experts
    each token picks, not the dense all-expert compute of this reference;
    attention pairs as half the causal square, or of the window's."""
    d, hd, nq, nkv = m["d_model"], m["head_dim"], m["num_heads"], \
        m["num_kv_heads"]
    e = m["moe"]
    f = 0.0
    for kind in layer_kinds(m):
        w = min(s, m["window_size"]) if kind == LOCAL else s
        pairs = s * w - w * w * 0.5
        f += 2 * b * s * d * hd * (nq + 2 * nkv) + 2 * b * s * nq * hd * d
        f += 2 * 2 * b * nq * pairs * hd
        f += 2 * b * s * d * e["num_experts"]
        f += 2 * b * s * e["top_k"] * 3 * d * e["d_ff"]
    return 3.0 * (f + 2 * b * s * d * m["vocab_size"])
