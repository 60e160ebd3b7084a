"""Attention: GQA/MHA, global or sliding-window, train / prefill / decode.

Three implementations, mirroring the paper's experimental arms (Table 3):
  * ``reference`` — plain jnp einsum attention ("none" in the paper),
  * ``recompute`` — same math under jax.checkpoint (applied at the block
    level, see blocks.py) — the paper's "recompute" arm,
  * ``flash``     — the Pallas flash-attention kernel (paper's
    "flash attn 2" arm). It compiles for the TPU; on the CPU backend,
    where Pallas TPU kernels cannot compile, it runs in interpret mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import _winit, apply_norm, cdtype, init_norm, rope, softcap

NEG_INF = -0.7 * float(np.finfo(np.float32).max)


def init_attention(key, cfg, cross=False):
    d, hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": _winit(ks[0], (d, nq, hd), d),
        "wk": _winit(ks[1], (d, nkv, hd), d),
        "wv": _winit(ks[2], (d, nkv, hd), d),
        "wo": _winit(ks[3], (nq, hd, d), nq * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq, hd), jnp.float32)
        p["bk"] = jnp.zeros((nkv, hd), jnp.float32)
        p["bv"] = jnp.zeros((nkv, hd), jnp.float32)
    if cfg.qk_norm:
        p["qnorm"] = init_norm(cfg, hd)
        p["knorm"] = init_norm(cfg, hd)
    if cross:
        p = {k: v for k, v in p.items() if k not in ("qnorm", "knorm")}
    return p


def _project_q(p, x, cfg, positions):
    dt = x.dtype
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
    if "qnorm" in p:
        q = apply_norm(p["qnorm"], q)
    if positions is not None:
        q = rope(q, positions, cfg.rope_theta)
    return q


def _project_kv(p, x, cfg, positions):
    dt = x.dtype
    k = jnp.einsum("bsd,dnh->bsnh", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dnh->bsnh", x, p["wv"].astype(dt))
    if "bk" in p:
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if "knorm" in p:
        k = apply_norm(p["knorm"], k)
    if positions is not None:
        k = rope(k, positions, cfg.rope_theta)
    return k, v


def _sdpa(q, k, v, cfg, q_pos, k_pos, *, causal, window):
    """Reference scaled-dot-product attention with additive masking.

    q: (b, sq, nq, hd); k/v: (b, sk, nkv, hd); *_pos: (b, s*) int32.
    Computed in fp32 (the paper's exp-(7) pathology: on GPU this upcast
    chain ran as separate unfused kernels; XLA fuses it — see DESIGN.md).
    """
    b, sq, nq, hd = q.shape
    nkv = k.shape[2]
    m = nq // nkv
    qr = q.reshape(b, sq, nkv, m, hd)
    score_dt = jnp.float32 if cfg.attn_fp32 else q.dtype
    scores = jnp.einsum("bqgmh,bkgh->bgmqk", qr, k).astype(score_dt)
    scores = scores / np.sqrt(hd).astype(score_dt)
    scores = softcap(scores, cfg.attn_softcap)
    mask = jnp.ones((b, 1, 1, sq, k.shape[1]), bool)
    dq = q_pos[:, None, None, :, None]
    dk = k_pos[:, None, None, None, :]
    if causal:
        mask &= dq >= dk
    if window:
        mask &= dq - dk < window
    mask &= dk >= 0  # ring-buffer slots not yet written carry pos=-1
    neg = jnp.asarray(NEG_INF, score_dt)
    scores = jnp.where(mask, scores, neg)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgmqk,bkgh->bqgmh", probs, v)
    return out.reshape(b, sq, nq, hd)


def _flash(q, k, v, cfg, *, causal, window, q_offset=0):
    from repro.kernels import ops  # lazy: kernels are optional at import
    return ops.flash_attention(
        q, k, v, causal=causal, window=window or 0,
        softcap=cfg.attn_softcap, q_offset=q_offset,
        interpret=jax.default_backend() == "cpu")


def attention(p, x, cfg, positions, *, kind, impl=None, causal=True):
    """Full-sequence (train / prefill) self attention.

    kind: 'attn' (global causal) or 'local_attn' (sliding window).
    causal=False gives bidirectional self-attention (whisper encoder).
    Returns (out, (k, v)) so prefill can build the cache.
    """
    impl = impl or cfg.attn_impl
    q = _project_q(p, x, cfg, positions)
    k, v = _project_kv(p, x, cfg, positions)
    window = cfg.window_size if kind == "local_attn" else 0
    if impl == "flash" and causal:
        out = _flash(q, k, v, cfg, causal=True, window=window)
    else:
        out = _sdpa(q, k, v, cfg, positions, positions, causal=causal, window=window)
    dt = x.dtype
    out = jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(dt))
    return out, (k, v)


def attention_sliced(p, x, cfg, positions, kv_prefix, *, kind, impl=None):
    """Self-attention for ONE sequence slice with a retained-KV prefix
    (sequence-sliced schedules, docs/longcontext.md).

    x: (b, L, d) — the slice's tokens, whose global positions are
    ``positions`` (contiguous, starting at the prefix length).
    kv_prefix: (k, v) of shape (b, P, nkv, hd) — post-RoPE keys/values of
    ALL earlier slices (P = 0 for slice 0). The slice attends causally
    over prefix + itself; since the prefix covers global positions
    [0, P) and the slice [P, P+L), key positions are just arange(P+L).

    Returns (out, (k_own, v_own)) — the slice's own post-RoPE KV, which
    the executor retains for later slices' prefixes.
    """
    impl = impl or cfg.attn_impl
    q = _project_q(p, x, cfg, positions)
    k_own, v_own = _project_kv(p, x, cfg, positions)
    pk, pv = kv_prefix
    dt = x.dtype
    k = jnp.concatenate([pk.astype(dt), k_own], axis=1)
    v = jnp.concatenate([pv.astype(dt), v_own], axis=1)
    window = cfg.window_size if kind == "local_attn" else 0
    if impl == "flash":
        out = _flash(q, k, v, cfg, causal=True, window=window,
                     q_offset=int(pk.shape[1]))
    else:
        b, total_k = k.shape[0], k.shape[1]
        k_pos = jnp.broadcast_to(
            jnp.arange(total_k, dtype=jnp.int32)[None], (b, total_k))
        out = _sdpa(q, k, v, cfg, positions, k_pos, causal=True,
                    window=window)
    out = jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(dt))
    return out, (k_own, v_own)


def cross_attention(p, x, enc_states, cfg):
    """Decoder->encoder attention (whisper). Projects k/v from the encoder
    hidden states with this layer's weights (no RoPE across modalities)."""
    q = _project_q(p, x, cfg, None)
    k, v = _project_kv(p, enc_states.astype(x.dtype), cfg, None)
    b, sq = x.shape[:2]
    q_pos = jnp.zeros((b, sq), jnp.int32)
    k_pos = jnp.zeros((b, k.shape[1]), jnp.int32)
    out = _sdpa(q, k, v, cfg, q_pos, k_pos, causal=False, window=0)
    return jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(x.dtype))


# ---------------------------------------------------------------------------
# Decode step with KV cache
# ---------------------------------------------------------------------------
def init_kv_cache(cfg, kind, batch, max_len, dtype):
    """Global layers cache max_len slots; local layers a ring of window."""
    n = min(cfg.window_size, max_len) if kind == "local_attn" else max_len
    shape = (batch, n, cfg.num_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        # position stored in each slot; -1 = empty
        "pos": jnp.full((batch, n), -1, jnp.int32),
    }


def update_kv_cache(cache, k_new, v_new, pos):
    """Write one token (b, 1, nkv, hd) at position ``pos`` (scalar int32)."""
    n = cache["k"].shape[1]
    slot = pos % n
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, slot, axis=1)
    b = cache["pos"].shape[0]
    ppos = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], jnp.full((b, 1), pos, jnp.int32), slot, axis=1)
    return {"k": k, "v": v, "pos": ppos}


def fill_kv_cache(cache, k_seq, v_seq, start=0):
    """Bulk write a prefill sequence (b, s, nkv, hd) into the cache tail."""
    n = cache["k"].shape[1]
    s = k_seq.shape[1]
    b = k_seq.shape[0]
    if s >= n:  # keep last n positions (ring for local layers)
        k_keep, v_keep = k_seq[:, -n:], v_seq[:, -n:]
        pos = jnp.broadcast_to(jnp.arange(s - n, s, dtype=jnp.int32)[None], (b, n))
        # ring alignment: position p lives at slot p % n
        roll = (s - n) % n
        return {"k": jnp.roll(k_keep, roll, axis=1),
                "v": jnp.roll(v_keep, roll, axis=1),
                "pos": jnp.roll(pos + start, roll, axis=1)}
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_seq, 0, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_seq, 0, axis=1)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s)) + start
    ppos = jax.lax.dynamic_update_slice_in_dim(cache["pos"], pos, 0, axis=1)
    return {"k": k, "v": v, "pos": ppos}


def attention_decode(p, x, cfg, cache, pos, *, kind):
    """One-token decode: x (b, 1, d), pos scalar. Returns (out, cache)."""
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q = _project_q(p, x, cfg, positions)
    k_new, v_new = _project_kv(p, x, cfg, positions)
    cache = update_kv_cache(cache, k_new, v_new, pos)
    window = cfg.window_size if kind == "local_attn" else 0
    out = _sdpa(q, cache["k"], cache["v"], cfg, positions, cache["pos"],
                causal=True, window=window)
    out = jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(x.dtype))
    return out, cache
