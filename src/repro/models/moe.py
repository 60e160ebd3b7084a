"""Mixture-of-Experts FFN: a top-k router, then one of two dispatches.

Capacity-bounded (DESIGN.md §3): tokens are dispatched into a
per-batch-row expert buffer (b, E, C, d) with scatter-drop semantics,
experts run as one batched einsum (MXU-friendly, E shardable over the
``model`` mesh axis => GSPMD inserts the all-to-all), and results are
gathered back and combined with router gates. FLOPs are exactly
``top_k * capacity_factor`` times the dense-equivalent FFN — no dense
all-experts waste.

Dropless (``MoEConfig.dropless``): every pick is computed. The picks are
sorted by expert and the experts this layer holds (``held`` of them from
``first_held``; all by default) run as grouped matmuls
(``kernels.ops.grouped_matmul``) over their own rows only; the layer
routes over every expert and adds only its own experts' part, as one
expert-parallel shard would before its exchange.

Routers: softmax top-k with renormalised gates and the Switch aux loss,
or DeepSeek-V3's sigmoid scores with a selection bias (``route_bias``)
added for the choice only, the chosen scores normalised and scaled by
``routed_scale``, and no aux loss. Shared experts (``shared_experts`` of
``d_ff``) run as one SwiGLU on every token.

Named scopes ``moe.route``, ``moe.dispatch``, ``moe.experts``,
``moe.combine`` and ``moe.shared`` mark the parts in a profile.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import _winit, apply_mlp


def init_moe(key, cfg):
    d, e = cfg.d_model, cfg.moe
    ks = jax.random.split(key, 5)
    h = e.n_held
    p = {
        "router": _winit(ks[0], (d, e.num_experts), d),
        "wi": _winit(ks[1], (h, d, e.d_ff), d),
        "wg": _winit(ks[2], (h, d, e.d_ff), d),
        "wo": _winit(ks[3], (h, e.d_ff, d), e.d_ff),
    }
    if e.scoring == "sigmoid":  # published init, trained outside the grad
        p["route_bias"] = jnp.zeros((e.num_experts,), jnp.float32)
    if e.shared_experts:
        f = e.shared_experts * e.d_ff
        p["shared"] = {
            "wi": _winit(jax.random.fold_in(ks[4], 0), (d, f), d),
            "wg": _winit(jax.random.fold_in(ks[4], 1), (d, f), d),
            "wo": _winit(jax.random.fold_in(ks[4], 2), (f, d), f),
        }
    return p


def capacity(cfg, seq_len: int) -> int:
    e = cfg.moe
    c = int(np.ceil(seq_len * e.top_k / e.num_experts * e.capacity_factor))
    return max(e.top_k, min(c, seq_len * e.top_k))


def route(p, x, cfg):
    """Router in fp32. Returns (gates (b,s,k), experts (b,s,k), aux_loss)."""
    e = cfg.moe
    logits = (x.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    if e.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)                         # (b, s, E)
        _, idx = jax.lax.top_k(scores + p["route_bias"], e.top_k)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
        return gates * e.routed_scale, idx, jnp.zeros((), jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                     # (b, s, E)
    gates, idx = jax.lax.top_k(probs, e.top_k)                  # (b, s, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * P_e
    f = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, e.num_experts, dtype=jnp.float32), axis=2),
        axis=(0, 1))                                            # fraction routed
    pbar = jnp.mean(probs, axis=(0, 1))
    aux = e.num_experts * jnp.sum(f * pbar)
    return gates, idx, aux


def apply_moe(p, x, cfg):
    """x: (b, s, d) -> (y, aux_loss)."""
    e = cfg.moe
    with jax.named_scope("moe.route"):
        gates, idx, aux = route(p, x, cfg)
    if e.dropless:
        y = _dropless(p, x, gates, idx, cfg)
    else:
        y = _capacity(p, x, gates, idx, cfg)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            y = y + apply_mlp(p["shared"], x, cfg)
    return y, aux * e.router_aux_weight


def _dropless(p, x, gates, idx, cfg):
    """Every pick of a held expert computed: picks sorted by expert, the
    held experts' rows through grouped matmuls, weighted by their gates
    and summed back onto their tokens."""
    from repro.kernels import ops  # lazy: kernels are optional at import
    e = cfg.moe
    b, s, d = x.shape
    dt = x.dtype
    lo, hi = e.first_held, e.first_held + e.n_held
    with jax.named_scope("moe.dispatch"):
        flat = idx.reshape(-1)                                   # (b*s*k,)
        order = jnp.argsort(flat, stable=True)
        expert = flat[order]
        mine = (expert >= lo) & (expert < hi)
        sizes = jnp.bincount(flat, length=e.num_experts).astype(jnp.int32)
        token = order // e.top_k
        rows = jnp.where(mine[:, None], x.reshape(-1, d)[token], 0)
    with jax.named_scope("moe.experts"):
        interpret = jax.default_backend() == "cpu"

        def gmm(lhs, w):
            return ops.grouped_matmul(lhs, w.astype(dt), sizes, lo,
                                      interpret=interpret)
        h = jax.nn.silu(gmm(rows, p["wi"])) * gmm(rows, p["wg"])
        out = gmm(h, p["wo"])
    with jax.named_scope("moe.combine"):
        w = jnp.where(mine, gates.reshape(-1)[order], 0.0)
        out = jnp.where(mine[:, None], out.astype(jnp.float32), 0.0)
        y = jnp.zeros((b * s, d), jnp.float32).at[token].add(out * w[:, None])
    return y.reshape(b, s, d).astype(dt)


def _capacity(p, x, gates, idx, cfg):
    """Picks past each expert's capacity in the row are dropped."""
    e = cfg.moe
    b, s, d = x.shape
    k, E = e.top_k, e.num_experts
    C = capacity(cfg, s)

    # --- position of each (token, k) inside its expert's buffer, per row ---
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)            # (b, s, k, E)
    flatoh = onehot.reshape(b, s * k, E)
    slots = jnp.cumsum(flatoh, axis=1) * flatoh - 1             # (b, s*k, E)
    slot = jnp.sum(slots * flatoh, axis=-1).reshape(b, s, k)    # (b, s, k)
    dropped = slot >= C
    slot = jnp.where(dropped, C, slot)                          # C = drop bin

    # --- dispatch: scatter tokens into (b, E, C, d) ---
    bi = jnp.broadcast_to(jnp.arange(b)[:, None, None], (b, s, k))
    buf = jnp.zeros((b, E, C + 1, d), x.dtype)
    x_rep = jnp.broadcast_to(x[:, :, None, :], (b, s, k, d))
    if cfg.moe_constrained:
        # §Perf (moe_a2a): keep the scatter entirely batch-local (E and C
        # replicated within a data shard), then reshard the dispatched
        # buffer to expert-parallel in ONE step — GSPMD lowers that
        # boundary as the canonical MoE all-to-all instead of gathering
        # the scatter operands across the mesh.
        from repro.sharding.rules import maybe_constrain
        batch_only = lambda t: maybe_constrain(
            t, ("pod", "data"), *([None] * (t.ndim - 1)))
        buf = batch_only(buf)
        x_rep = batch_only(x_rep)
    buf = buf.at[bi, idx, slot].set(x_rep, mode="drop")
    buf = buf[:, :, :C]                                         # drop bin off

    if cfg.moe_constrained:  # expert-parallel boundary: the all-to-all
        buf = maybe_constrain(buf, ("pod", "data"), "model", None, None)

    # --- expert computation: batched einsum, E shardable over "model" ---
    dt = x.dtype
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", buf, p["wi"].astype(dt)))
    h = h * jnp.einsum("becd,edf->becf", buf, p["wg"].astype(dt))
    out = jnp.einsum("becf,efd->becd", h, p["wo"].astype(dt))   # (b, E, C, d)
    if cfg.moe_constrained:
        out = maybe_constrain(out, ("pod", "data"), "model", None, None)

    # --- combine: gather back + weight by gates ---
    out = jnp.pad(out, ((0, 0), (0, 0), (0, 1), (0, 0)))        # drop bin = 0
    y = out[bi, idx, slot]                                      # (b, s, k, d)
    return jnp.sum(y * gates[..., None].astype(dt), axis=2)     # (b, s, d)
