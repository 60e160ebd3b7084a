"""Reference of the DeepSeek-V3 decoder (Hugging Face's ``deepseek_v3``, as
Moonlight-16B-A3B publishes it) at the configuration file's ``model``
sizes, on one expert-parallel share.

Every layer is pre-norm (RMSNorm, eps ``norm_eps``) multi-head latent
attention followed by an FFN: a SwiGLU of ``d_ff`` on the
``first_k_dense`` leading layers (kind ``dense``), a mixture of experts on
the rest (kind ``moe``). ``layers[kind]`` holds each kind's stack.

MLA with no query LoRA: ``q = h Wq`` gives ``head_dim`` (no rotary) +
``qk_rope_head_dim`` (rotary) per head; ``[c, k_pe] = h Wkv_a`` gives the
``kv_lora_rank`` latent and one rotary key shared by the heads;
``[k_nope, v] = RMSNorm(c) Wkv_b``. Rotary positions (theta
``rope_theta``) use HF's layout: each rotary vector is permuted out of
interleaved order (even dims, then odd) before rotate-half. Causal
softmax attention at scale 1/sqrt(head_dim + qk_rope_head_dim) over
values ``v_head_dim`` wide, then ``Wo``.

MoE (``moe`` block): scores ``sigmoid(h Wr)`` over all ``num_experts``;
the top ``top_k`` of ``scores + route_bias`` are chosen; their gates are
the chosen scores normalised to sum 1, times ``routed_scale``. The
experts held here, ``[first_held, first_held + held)``, are computed
densely on every token and weighted by their gate (0 where not picked):
the share of one chip of an expert-parallel deployment, the other chips'
experts left out. ``shared_experts`` experts of ``d_ff`` run as one
SwiGLU on every token and are added once.

Departures from HF's ``modeling_deepseek.py``, all deliberate:

- ``route_bias`` (HF's ``e_score_correction_bias``) is a seeded leaf,
  small and random where the published initial value is zero, so that a
  run tests the choice apart from the gate values. It enters the choice
  only, so its gradient is zero; HF trains it outside the gradient, and
  that update rule is not modelled (nor decayed: not in ``DECAYED``).
- No sequence-wise auxiliary loss (``seq_aux``): a training-recipe term
  outside the published config's forward pass.
- Only the held experts' part of the routed output is added; group
  routing is absent, as ``n_group`` = ``topk_group`` = 1 makes it trivial.
- No multi-token-prediction layers (``num_nextn_predict_layers`` 0) and
  no decode cache: the reference is the training forward and loss.

The interface is ``bench/models/__init__.py``'s; nothing here imports the
program. ``to_program`` and ``from_program`` re-key the weights into
``models.model``'s layout: the dense layers as ``blocks.lead<i>``, the
MoE layers as the one scanned block ``blocks.pos0``. ``attention_work``
and ``expert_work`` count what the ``mla_flash_roofline`` and
``moe_expert_roofline`` metrics divide by the chip's peaks;
``held_load`` counts the picks that land on the held experts, which the
``executor_routing`` entry prints and the expert roofline counts its work
at.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.models import qwen2
from bench.reference import HIGHEST, mm

DENSE, MOE = "dense", "moe"
#: the leaves weight decay applies to: weight matrices and the router; no
#: norm scale and not the selection bias
DECAYED = frozenset({"embed", "unembed", "wq", "wkv_a", "wkv_b", "wo", "wi",
                     "wg", "w2", "router", "shared_wi", "shared_wg",
                     "shared_w2"})
embed = qwen2.embed


def layer_kinds(m: Dict):
    k = m["first_k_dense"]
    return (DENSE,) * k + (MOE,) * (m["num_layers"] - k)


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------
def param_shapes(m: Dict) -> Dict:
    D, H, V = m["d_model"], m["num_heads"], m["vocab_size"]
    nope, rot, r, vd = (m["head_dim"], m["qk_rope_head_dim"],
                        m["kv_lora_rank"], m["v_head_dim"])
    e = m["moe"]
    E, Eh, Fe = e["num_experts"], e["held"], e["d_ff"]
    Fs = e["shared_experts"] * Fe
    kinds = layer_kinds(m)
    layers = {}
    for kind in dict.fromkeys(kinds):
        n = kinds.count(kind)
        lay = {"ln1_scale": (n, D), "wq": (n, D, H, nope + rot),
               "wkv_a": (n, D, r + rot), "kv_scale": (n, r),
               "wkv_b": (n, r, H, nope + vd), "wo": (n, H, vd, D),
               "ln2_scale": (n, D)}
        if kind == DENSE:
            F = m["d_ff"]
            lay.update(wi=(n, D, F), wg=(n, D, F), w2=(n, F, D))
        else:
            lay.update(router=(n, D, E), route_bias=(n, E),
                       wi=(n, Eh, D, Fe), wg=(n, Eh, D, Fe),
                       w2=(n, Eh, Fe, D), shared_wi=(n, D, Fs),
                       shared_wg=(n, D, Fs), shared_w2=(n, Fs, D))
        layers[kind] = lay
    return {"embed": (V, D), "unembed": (D, V),
            "final_norm": {"scale": (D,)}, "layers": layers}


def fan_in(name: str, shape) -> Optional[int]:
    """Fan-in of a weight; None for norm scales and the selection bias.
    Layer leaves carry the leading layer axis, expert leaves an expert
    axis after it."""
    if name == "embed":
        return shape[-1]
    if name == "unembed":
        return shape[0]
    if name in ("wi", "wg", "w2"):
        return shape[2] if len(shape) == 4 else shape[1]
    if name == "wo":
        return shape[1] * shape[2]
    if name in ("wq", "wkv_a", "wkv_b", "router", "shared_wi", "shared_wg",
                "shared_w2"):
        return shape[1]
    return None


# ---------------------------------------------------------------------------
# Forward and loss
# ---------------------------------------------------------------------------
def rms_norm(m, x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + m["norm_eps"]) * scale


def rope(x, theta):
    """HF DeepSeek-V3's rotary embedding of x (b, s, n, d): the vector is
    permuted out of interleaved order, then rotated half against half."""
    return qwen2.rope(jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1),
                      theta)


def mla(m, quant, lp, h):
    """Causal multi-head latent attention of the normed input ``h``."""
    nope, r = m["head_dim"], m["kv_lora_rank"]
    q = mm("bsd,dnh->bsnh", h, lp["wq"], quant)
    kv = mm("bsd,dk->bsk", h, lp["wkv_a"], quant)
    c = rms_norm(m, kv[..., :r], lp["kv_scale"])
    kvb = mm("bsr,rnh->bsnh", c, lp["wkv_b"], quant)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    q_pe = rope(q[..., nope:], m["rope_theta"])
    k_pe = rope(kv[..., None, r:], m["rope_theta"])
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe, k_nope.shape[:3] + k_pe.shape[-1:])], -1)
    return mm("bsnh,nhd->bsd", attend(quant, q, k, v), lp["wo"], quant)


def attend(quant, q, k, v):
    """Causal softmax attention of q, k (b, s, n, hq) and v (b, s, n, hv),
    one head at a time and each head recomputed in the backward, so that a
    row's (s, s) scores are held for one head, not for all. float8 rounds
    q, k and v as whole tensors, as one product over all heads would, and
    each head's probabilities, whose largest is 1 in every head as in the
    whole tensor (the first query sees only the first key)."""
    s = q.shape[1]
    keep = np.tril(np.ones((s, s), bool))
    if quant is not None:
        q, k, v = quant(q), quant(k), quant(v)

    def head(qkv):
        qh, kh, vh = qkv
        sc = mm("bqh,bkh->bqk", qh, kh, None) / math.sqrt(qh.shape[-1])
        p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
        return mm("bqk,bkh->bqh", p if quant is None else quant(p), vh, None)

    o = jax.lax.map(jax.checkpoint(head),
                    tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))
    return jnp.moveaxis(o, 0, 2)


def gates(m, lp, h, quant=None):
    """(gates (b, s, top_k), picks (b, s, top_k)) of the sigmoid router:
    the choice by ``scores + route_bias``, the gates from the scores."""
    e = m["moe"]
    scores = jax.nn.sigmoid(mm("bsd,de->bse", h, lp["router"], quant))
    _, idx = jax.lax.top_k(scores + lp["route_bias"], e["top_k"])
    top = jnp.take_along_axis(scores, idx, -1)
    return top / (jnp.sum(top, -1, keepdims=True) + 1e-20) \
        * e["routed_scale"], idx


def moe(m, quant, lp, h):
    """The held experts' part of the routed output plus the shared
    experts, for the normed input ``h``."""
    e = m["moe"]
    lo = e["first_held"]
    g, idx = gates(m, lp, h, quant)
    picked = jax.nn.one_hot(idx, e["num_experts"], dtype=jnp.float32)
    gate = jnp.einsum("bske,bsk->bse", picked, g,
                      precision=HIGHEST)[..., lo:lo + e["held"]]
    u = jax.nn.silu(mm("bsd,edf->bsef", h, lp["wi"], quant)) \
        * mm("bsd,edf->bsef", h, lp["wg"], quant)
    y = mm("bsef,efd->bsed", u, lp["w2"], quant)
    out = jnp.einsum("bsed,bse->bsd", y, gate, precision=HIGHEST)
    return out + qwen2.swiglu(quant, h, lp["shared_wi"], lp["shared_wg"],
                              lp["shared_w2"])


def layer(m, kind, quant, lp, x):
    x = x + mla(m, quant, lp, rms_norm(m, x, lp["ln1_scale"]))
    h = rms_norm(m, x, lp["ln2_scale"])
    if kind == DENSE:
        return x + qwen2.swiglu(quant, h, lp["wi"], lp["wg"], lp["w2"]), None
    return x + moe(m, quant, lp, h), None


def held_load(m: Dict, t: Dict, tokens) -> Dict:
    """Routing counts of the forward over ``tokens`` (b, s) on the weights
    ``t``: of the ``picks`` of every MoE layer, the ``held_picks`` that
    land on the held experts, and the largest and mean number of picks a
    held expert gets in one row of one layer (``max_load``,
    ``mean_load``)."""
    e = m["moe"]
    lo = e["first_held"]
    x, counts, seen = embed(t, tokens), [], {}
    for kind in layer_kinds(m):
        i = seen[kind] = seen.get(kind, -1) + 1
        lp = jax.tree.map(lambda a: a[i], t["layers"][kind])
        if kind == MOE:
            h = x + mla(m, None, lp, rms_norm(m, x, lp["ln1_scale"]))
            _, idx = gates(m, lp, rms_norm(m, h, lp["ln2_scale"]))
            counts.append(jax.vmap(lambda r: jnp.bincount(
                r.reshape(-1), length=e["num_experts"]))(idx))
        x, _ = layer(m, kind, None, lp, x)
    held = jnp.stack(counts)[..., lo:lo + e["held"]]    # (layers, b, held)
    return {"held_picks": held.sum(),
            "picks": jnp.asarray(len(counts) * tokens.size * e["top_k"]),
            "max_load": held.max(), "mean_load": held.mean()}


def head_loss(m, quant, head, x, labels):
    """Mean next-token cross-entropy over every token of the rows."""
    x = rms_norm(m, x, head["final_norm"]["scale"])
    logits = mm("bsd,dv->bsv", x, head["unembed"], quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)


# ---------------------------------------------------------------------------
# The program's layout
# ---------------------------------------------------------------------------
#: bench layer leaf -> its path inside one program layer
LAYER_KEYS = {"ln1_scale": ("norm1", "scale"), "ln2_scale": ("norm2", "scale"),
              "wq": ("mixer", "wq"), "wkv_a": ("mixer", "wkv_a"),
              "kv_scale": ("mixer", "kv_norm", "scale"),
              "wkv_b": ("mixer", "wkv_b"), "wo": ("mixer", "wo"),
              "wi": ("ffn", "wi"), "wg": ("ffn", "wg"), "w2": ("ffn", "wo"),
              "router": ("ffn", "router"), "route_bias": ("ffn", "route_bias"),
              "shared_wi": ("ffn", "shared", "wi"),
              "shared_wg": ("ffn", "shared", "wg"),
              "shared_w2": ("ffn", "shared", "wo")}


def _check(cfg):
    e = cfg.moe
    if (tuple(cfg.block_pattern) != ("mla",) or e is None
            or e.scoring != "sigmoid" or not e.dropless):
        raise ValueError(f"deepseek_v3 reference: {cfg.name} is not MLA "
                         "layers with dropless sigmoid-routed experts")


def _program_layer(t_kind):
    layer = {}
    for k, a in t_kind.items():
        *path, key = LAYER_KEYS[k]
        node = layer
        for p in path:
            node = node.setdefault(p, {})
        node[key] = a
    return layer


def _bench_layer(p_layer):
    out = {}
    for k, path in LAYER_KEYS.items():
        node = p_layer
        for p in path:
            node = node.get(p, {}) if isinstance(node, dict) else {}
        if not isinstance(node, dict):
            out[k] = node
    return out


def to_program(t, cfg):
    """The benchmark's layout -> ``models.model``'s (a re-keying)."""
    _check(cfg)
    blocks = {"pos0": _program_layer(t["layers"][MOE])}
    for i in range(cfg.first_k_dense):
        blocks[f"lead{i}"] = _program_layer(
            jax.tree.map(lambda a: a[i], t["layers"][DENSE]))
    return {"embed": {"table": t["embed"], "unembed": t["unembed"]},
            "blocks": blocks, "final_norm": t["final_norm"]}


def from_program(p, cfg):
    """``models.model``'s layout -> the benchmark's."""
    _check(cfg)
    dense = [_bench_layer(p["blocks"][f"lead{i}"])
             for i in range(cfg.first_k_dense)]
    return {"embed": p["embed"]["table"], "unembed": p["embed"]["unembed"],
            "final_norm": p["final_norm"],
            "layers": {DENSE: jax.tree.map(lambda *a: jnp.stack(a), *dense),
                       MOE: _bench_layer(p["blocks"]["pos0"])}}


# ---------------------------------------------------------------------------
# Work
# ---------------------------------------------------------------------------
def _attention_matmul_flops(m: Dict, b: int, s: int) -> float:
    """Forward FLOPs of one layer's causal QK^T and PV (half the square)."""
    qk = m["head_dim"] + m["qk_rope_head_dim"]
    return 2.0 * b * m["num_heads"] * s * s * (qk + m["v_head_dim"]) * 0.5


def model_flops_train(m: Dict, b: int, s: int) -> float:
    """Matmul FLOPs of one training step over b rows of s tokens: forward
    + backward = 3 x forward, causal attention as half the square, the
    logits once, recomputation left out; the held experts at their
    expected share of the picks, ``top_k * held / num_experts`` a token."""
    d, H = m["d_model"], m["num_heads"]
    nope, rot, r, vd = (m["head_dim"], m["qk_rope_head_dim"],
                        m["kv_lora_rank"], m["v_head_dim"])
    e = m["moe"]
    attn = (2 * b * s * d * H * (nope + rot) + 2 * b * s * d * (r + rot)
            + 2 * b * s * r * H * (nope + vd) + 2 * b * s * H * vd * d
            + _attention_matmul_flops(m, b, s))
    dense = 2 * b * s * 3 * d * m["d_ff"]
    picks = e["top_k"] * e["held"] / e["num_experts"]
    moe_ffn = (2 * b * s * d * e["num_experts"]
               + 2 * b * s * picks * 3 * d * e["d_ff"]
               + 2 * b * s * e["shared_experts"] * 3 * d * e["d_ff"])
    f = sum(attn + (dense if kind == DENSE else moe_ffn)
            for kind in layer_kinds(m))
    return 3.0 * (f + 2 * b * s * d * m["vocab_size"])


def attention_work(m: Dict, b: int, s: int, itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one causal MLA attention forward + backward call
    over b rows of s tokens, q/k ``head_dim + qk_rope_head_dim`` wide and
    v ``v_head_dim``: QK^T and PV forward, dQ, dK, dP and dV backward, no
    recomputation; each tensor read or written once at ``itemsize`` bytes
    (forward reads q, k, v and writes o; backward reads q, k, v, o, do
    and writes dq, dk, dv), the fp32 row statistic written and read
    once."""
    H = m["num_heads"]
    qk = m["head_dim"] + m["qk_rope_head_dim"]
    flops = 3.0 * _attention_matmul_flops(m, b, s)
    q_bytes = b * s * H * qk * itemsize             # q, k, dq, dk alike
    v_bytes = b * s * H * m["v_head_dim"] * itemsize  # v, o, do, dv alike
    fwd = 2 * q_bytes + 2 * v_bytes
    bwd = 4 * q_bytes + 4 * v_bytes
    return flops, float(fwd + bwd + 2 * b * H * s * 4)


def expert_work(m: Dict, b: int, s: int, itemsize: int = 2,
                rows: Optional[float] = None) -> tuple:
    """(FLOPs, bytes) of one MoE layer's grouped expert matmuls, forward +
    backward, no recomputation, over ``rows`` picks of the held experts
    (by default their expected share of b rows of s tokens,
    ``b * s * top_k * held / num_experts``). Three grouped matmuls (gate,
    up: d -> d_ff; down: d_ff -> d), each K -> N over R rows with W =
    held * K * N weights: forward reads R*K and W and writes R*N; the
    input gradient reads R*N and W and writes R*K; the weight gradient
    reads R*K and R*N and writes W."""
    e = m["moe"]
    d, f = m["d_model"], e["d_ff"]
    if rows is None:
        rows = b * s * e["top_k"] * e["held"] / e["num_experts"]
    flops = 3 * 3 * 2.0 * rows * d * f
    w = e["held"] * d * f
    per_matmul = 3 * rows * (d + f) + 3 * w
    return flops, float(3 * per_matmul * itemsize)
