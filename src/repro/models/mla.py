"""Multi-head latent attention (DeepSeek-V2/V3 MLA), train-time forward.

With no query LoRA (Moonlight-16B-A3B's ``q_lora_rank`` null):

    q = x Wq                          (heads, head_dim + qk_rope_head_dim)
    [c_kv, k_pe] = x Wkv_a            (kv_lora_rank + qk_rope_head_dim)
    [k_nope, v] = RMSNorm(c_kv) Wkv_b (heads, head_dim + v_head_dim)
    q = [q_nope, rope(q_pe)], k = [k_nope, rope(k_pe)]

``k_pe`` is one rotary key shared by every head. The rotary part uses the
published layout: Hugging Face's DeepSeek-V3 permutes each rotary vector
out of interleaved order (even dims, then odd) before rotate-half. The
scale is 1/sqrt(head_dim + qk_rope_head_dim); values are ``v_head_dim``
wide, so causal attention runs through the flash kernel with a value head
narrower than the query/key head (``attn_impl="flash"``) or through the
reference ``_sdpa``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.attention import _flash, _sdpa
from repro.models.layers import _winit, apply_norm, init_norm, rope


def init_mla(key, cfg):
    d, nq = cfg.d_model, cfg.num_heads
    nope, rot, r = cfg.head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    ks = jax.random.split(key, 4)
    return {
        "wq": _winit(ks[0], (d, nq, nope + rot), d),
        "wkv_a": _winit(ks[1], (d, r + rot), d),
        "kv_norm": init_norm(cfg, r),
        "wkv_b": _winit(ks[2], (r, nq, nope + cfg.v_dim), r),
        "wo": _winit(ks[3], (nq, cfg.v_dim, d), nq * cfg.v_dim),
    }


def _deinterleave(x):
    """(..., 2i + j) -> (..., j * half + i): HF DeepSeek-V3's permutation
    of a rotary vector before rotate-half."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def mla(p, x, cfg, positions, *, impl=None):
    """Causal self attention of the normed input ``x`` (b, s, d)."""
    impl = impl or cfg.attn_impl
    dt = x.dtype
    nope, r = cfg.head_dim, cfg.kv_lora_rank
    with jax.named_scope("mla"):
        q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"].astype(dt))
        kv = x @ p["wkv_a"].astype(dt)
        c = apply_norm(p["kv_norm"], kv[..., :r], cfg.norm_eps)
        kvb = jnp.einsum("bsr,rnh->bsnh", c, p["wkv_b"].astype(dt))
        k_nope, v = kvb[..., :nope], kvb[..., nope:]
        q_pe = rope(_deinterleave(q[..., nope:]), positions, cfg.rope_theta)
        k_pe = rope(_deinterleave(kv[..., None, r:]), positions,
                    cfg.rope_theta)
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:3]
                                      + k_pe.shape[-1:])], axis=-1)
        if impl == "flash":
            out = _flash(q, k, v, cfg, causal=True, window=0)
        else:
            out = _sdpa(q, k, v, cfg, positions, positions, causal=True,
                        window=0)
        return jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(dt))
