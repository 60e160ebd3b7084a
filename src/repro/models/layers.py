"""Shared building blocks: norms, rotary, FFNs, init helpers.

Pure-functional: params are plain dict pytrees of jnp arrays; every layer
is ``init_*(key, cfg) -> params`` + ``apply(params, x, ...) -> y``.
Compute dtype is bf16 (paper's mixed precision); norms/softmax in fp32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def cdtype(cfg):
    return jnp.dtype(cfg.dtype)


def softcap(x, cap: float):
    """gemma2-style logit soft-capping: cap * tanh(x / cap)."""
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def init_norm(cfg, d=None):
    d = d or cfg.d_model
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32)}


def apply_norm(params, x, eps=1e-6):
    xf = x.astype(jnp.float32)
    if "bias" in params:  # layernorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * params["scale"]
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------
def rope(x, positions, theta: float):
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-np.arange(0, half, dtype=np.float32) / half)
    ang = positions[..., :, None].astype(jnp.float32) * freq  # (..., s, half)
    ang = ang[..., None, :]                                   # (..., s, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense / FFN
# ---------------------------------------------------------------------------
def _winit(key, shape, in_dim, dtype=jnp.float32):
    return (jax.random.normal(key, shape, jnp.float32) / np.sqrt(in_dim)).astype(dtype)


def init_mlp(key, cfg):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_kind == "swiglu":
        return {"wi": _winit(ks[0], (d, f), d),
                "wg": _winit(ks[1], (d, f), d),
                "wo": _winit(ks[2], (f, d), f)}
    return {"wi": _winit(ks[0], (d, f), d),
            "wo": _winit(ks[2], (f, d), f)}


def apply_mlp(params, x, cfg):
    dt = x.dtype
    if "wg" in params:  # swiglu
        h = jax.nn.silu(x @ params["wi"].astype(dt)) * (x @ params["wg"].astype(dt))
    else:
        h = jax.nn.gelu(x @ params["wi"].astype(dt))
    return h @ params["wo"].astype(dt)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------
def init_embed(key, cfg):
    p = {"table": _winit(key, (cfg.vocab_size, cfg.d_model), cfg.d_model)}
    if not cfg.tie_embeddings:
        p["unembed"] = _winit(jax.random.fold_in(key, 1),
                              (cfg.d_model, cfg.vocab_size), cfg.d_model)
    return p


def embed(params, tokens, cfg):
    # Gather the fp32 rows, then cast: the table's gradient is then a
    # scatter-add in fp32. Cast first, it adds in bf16, and a token that
    # fills much of a batch loses its gradient to rounding.
    x = params["table"][tokens].astype(cdtype(cfg))
    if cfg.tie_embeddings:  # gemma-style scaled embeddings
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    return x


def unembed(params, x, cfg):
    if cfg.tie_embeddings:
        logits = x @ params["table"].astype(x.dtype).T
    else:
        logits = x @ params["unembed"].astype(x.dtype)
    logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return logits
