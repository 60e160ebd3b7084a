"""PatternStack: heterogeneous layer stacks as a scan over repeating blocks.

A model's depth is ``num_layers`` layers whose temporal-mixer kinds follow
``cfg.block_pattern`` (e.g. recurrentgemma: (RGLRU, RGLRU, LOCAL)).
Full pattern repetitions are stacked (leading dim ``n_full``) and iterated
with ``lax.scan`` — HLO stays O(pattern), not O(depth), which keeps the
512-device dry-run compiles fast. Remainder layers (depth % pattern) are
unrolled at the end.

Each layer = mixer + optional cross-attention (enc-dec) + FFN (dense or
MoE), pre-norm residual. ``cfg.first_k_dense`` leading layers (``lead<i>``,
unrolled ahead of the scan) take a dense FFN of ``d_ff`` where the
pattern's layers take the MoE (DeepSeek-V3's first_k_dense_replace).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, LOCAL, MLA, MLSTM, RGLRU, SLSTM
from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import recurrent as rec_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import apply_mlp, apply_norm, init_mlp, init_norm


# ---------------------------------------------------------------------------
# Single-layer init / apply
# ---------------------------------------------------------------------------
def init_layer(key, cfg, kind, *, cross=False, dense=False):
    """One layer's params; ``dense`` gives a MoE config's layer the dense
    FFN of ``d_ff`` (a leading dense layer)."""
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm1": init_norm(cfg)}
    if kind in (ATTN, LOCAL):
        p["mixer"] = attn_mod.init_attention(ks[0], cfg)
    elif kind == MLA:
        p["mixer"] = mla_mod.init_mla(ks[0], cfg)
    elif kind == RGLRU:
        p["mixer"] = rec_mod.init_rglru_block(ks[0], cfg)
    elif kind == MLSTM:
        p["mixer"] = xlstm_mod.init_mlstm(ks[0], cfg)
    elif kind == SLSTM:
        p["mixer"] = xlstm_mod.init_slstm(ks[0], cfg)
    if cross:
        p["norm_x"] = init_norm(cfg)
        p["cross"] = attn_mod.init_attention(ks[1], cfg, cross=True)
    if cfg.moe is not None and not dense:
        p["norm2"] = init_norm(cfg)
        p["ffn"] = moe_mod.init_moe(ks[2], cfg)
    elif cfg.d_ff:
        p["norm2"] = init_norm(cfg)
        p["ffn"] = init_mlp(ks[2], cfg)
    return p


def _apply_mixer(p, x, cfg, kind, positions, *, causal, remat):
    def f(p_, x_):
        if kind in (ATTN, LOCAL):
            out, _ = attn_mod.attention(p_, x_, cfg, positions, kind=kind,
                                        causal=causal)
            return out
        if kind == MLA:
            return mla_mod.mla(p_, x_, cfg, positions)
        if kind == RGLRU:
            return rec_mod.apply_rglru_block(p_, x_, cfg)
        if kind == MLSTM:
            return xlstm_mod.apply_mlstm_block(p_, x_, cfg)
        if kind == SLSTM:
            return xlstm_mod.apply_slstm_block(p_, x_, cfg)
        raise ValueError(kind)

    if remat == "attn" and kind in (ATTN, LOCAL, MLA):
        f = jax.checkpoint(f)
    return f(p, x)


#: Mixer kinds that support sequence slicing (seq_chunks > 1): causal
#: attention over a retained-KV prefix. Recurrent kinds (RGLRU, xLSTM)
#: carry cross-sequence state a slice boundary would sever.
SLICEABLE_KINDS = (ATTN, LOCAL)


def apply_layer_sliced(p, x, cfg, kind, positions, kv_prefix, *,
                       remat="none"):
    """One layer over ONE sequence slice with a retained-KV prefix
    (sequence-sliced schedules, docs/longcontext.md).

    Returns (x, aux_loss, (k, v)) — the slice's own post-RoPE KV, which
    the pipeline executor retains for later slices. Only attention
    mixers (``SLICEABLE_KINDS``) can slice; cross-attention layers
    cannot (the encoder states span the full sequence).
    """
    if kind not in SLICEABLE_KINDS:
        raise ValueError(
            f"seq_chunks > 1 needs attention mixers, got {kind!r}")
    if "cross" in p:
        raise ValueError("seq_chunks > 1 does not support cross-attention")

    def mix(p_, x_, kvp):
        return attn_mod.attention_sliced(p_, x_, cfg, positions, kvp,
                                         kind=kind)

    if remat == "attn":
        mix = jax.checkpoint(mix)
    aux = 0.0
    h, kv = mix(p["mixer"], apply_norm(p["norm1"], x, cfg.norm_eps),
                kv_prefix)
    x = x + h
    if "ffn" in p:
        h, aux = _ffn_sublayer(p, x, cfg)
        x = x + h
    return x, aux, kv


def _apply_ffn(p, x, cfg):
    if "router" in p:
        return moe_mod.apply_moe(p, x, cfg)
    return apply_mlp(p, x, cfg), 0.0


def _ffn_sublayer(p, x, cfg):
    """The FFN's residual branch of one layer: (branch output, aux).

    With a dropless MoE every layer's FFN (norm, router, experts, MLP) is
    recomputed in the backward instead of stashing its activations and
    bf16 weights per micro-batch: the dispatch buffers are sized for the
    worst case, top_k times the tokens."""
    def f(p_, x_):
        return _apply_ffn(p_["ffn"], apply_norm(p_["norm2"], x_,
                                                cfg.norm_eps), cfg)

    if cfg.moe is not None and cfg.moe.dropless:
        f = jax.checkpoint(f)
    return f({"ffn": p["ffn"], "norm2": p["norm2"]}, x)


def apply_layer(p, x, cfg, kind, positions, *, enc_states=None,
                causal=True, remat="none"):
    """Train/prefill layer. Returns (x, aux_loss)."""
    aux = 0.0
    h = _apply_mixer(p["mixer"], apply_norm(p["norm1"], x, cfg.norm_eps),
                     cfg, kind, positions, causal=causal, remat=remat)
    x = x + h
    if "cross" in p:
        xc = apply_norm(p["norm_x"], x, cfg.norm_eps)
        x = x + attn_mod.cross_attention(p["cross"], xc, enc_states, cfg)
    if "ffn" in p:
        h, aux = _ffn_sublayer(p, x, cfg)
        x = x + h
    return x, aux


# ---- per-layer recurrent/KV state ----------------------------------------
def init_layer_state(cfg, kind, batch, max_len, dtype):
    if kind in (ATTN, LOCAL):
        return attn_mod.init_kv_cache(cfg, kind, batch, max_len, dtype)
    if kind == RGLRU:
        return rec_mod.init_rglru_state(cfg, batch, dtype)
    if kind == MLSTM:
        return xlstm_mod.init_mlstm_state(cfg, batch)
    if kind == SLSTM:
        return xlstm_mod.init_slstm_state(cfg, batch)
    raise ValueError(kind)


def apply_layer_prefill(p, x, cfg, kind, positions, state, *, enc_states=None):
    """Like apply_layer but also fills this layer's decode state."""
    xn = apply_norm(p["norm1"], x, cfg.norm_eps)
    if kind in (ATTN, LOCAL):
        h, (k, v) = attn_mod.attention(p["mixer"], xn, cfg, positions, kind=kind)
        new_state = attn_mod.fill_kv_cache(state, k, v)
    elif kind == RGLRU:
        # run the block, then extract terminal recurrence/conv state
        h, new_state = _rglru_prefill(p["mixer"], xn, cfg, state)
    elif kind == MLSTM:
        hh, st = xlstm_mod.mlstm_chunkwise(p["mixer"], xn, cfg)
        h = jnp.einsum("bsnh,nhd->bsd", hh, p["mixer"]["wo"].astype(x.dtype))
        new_state = st
    elif kind == SLSTM:
        hh, st = xlstm_mod.slstm_scan(p["mixer"], xn, cfg)
        h = jnp.einsum("bsnh,nhd->bsd", hh, p["mixer"]["wo"].astype(x.dtype))
        new_state = st
    else:
        raise ValueError(kind)
    x = x + h
    if "cross" in p:
        xc = apply_norm(p["norm_x"], x, cfg.norm_eps)
        x = x + attn_mod.cross_attention(p["cross"], xc, enc_states, cfg)
    if "ffn" in p:
        h, _ = _apply_ffn(p["ffn"], apply_norm(p["norm2"], x, cfg.norm_eps),
                          cfg)
        x = x + h
    return x, new_state


def _rglru_prefill(p, xn, cfg, state):
    dt = xn.dtype
    u = xn @ p["in_x"].astype(dt)
    g = jax.nn.gelu(xn @ p["in_g"].astype(dt))
    uc = rec_mod._conv_full(p, u)
    h = rec_mod.rglru_scan(p, uc)
    out = (h * g) @ p["out"].astype(dt)
    cw = cfg.conv_width
    conv_tail = u[:, -(cw - 1):]
    pad = cw - 1 - conv_tail.shape[1]
    if pad > 0:
        conv_tail = jnp.pad(conv_tail, ((0, 0), (pad, 0), (0, 0)))
    new_state = {"h": h[:, -1].astype(jnp.float32), "conv": conv_tail}
    return out, new_state


def apply_layer_decode(p, x, cfg, kind, pos, state, *, enc_states=None):
    """One-token decode. x: (b, 1, d). Returns (x, new_state)."""
    xn = apply_norm(p["norm1"], x, cfg.norm_eps)
    if kind in (ATTN, LOCAL):
        h, state = attn_mod.attention_decode(p["mixer"], xn, cfg, state, pos, kind=kind)
    elif kind == RGLRU:
        h, state = rec_mod.apply_rglru_block_step(p["mixer"], xn, cfg, state)
    elif kind == MLSTM:
        h, state = xlstm_mod.apply_mlstm_block_step(p["mixer"], xn, cfg, state)
    elif kind == SLSTM:
        h, state = xlstm_mod.apply_slstm_block_step(p["mixer"], xn, cfg, state)
    else:
        raise ValueError(kind)
    x = x + h
    if "cross" in p:
        xc = apply_norm(p["norm_x"], x, cfg.norm_eps)
        x = x + attn_mod.cross_attention(p["cross"], xc, enc_states, cfg)
    if "ffn" in p:
        h, _ = _apply_ffn(p["ffn"], apply_norm(p["norm2"], x, cfg.norm_eps),
                          cfg)
        x = x + h
    return x, state


# ---------------------------------------------------------------------------
# PatternStack
# ---------------------------------------------------------------------------
class PatternStack:
    """Static helper describing how num_layers decompose into unrolled
    leading dense layers (the decoder's ``cfg.first_k_dense``), scanned
    pattern blocks and unrolled remainder layers."""

    def __init__(self, cfg, *, cross=False, num_layers=None, pattern=None):
        self.cfg = cfg
        self.cross = cross
        self.pattern = tuple(pattern or cfg.block_pattern)
        self.lead = cfg.first_k_dense if num_layers is None else 0
        n = num_layers if num_layers is not None else cfg.num_layers
        self.num_layers = n
        self.n_full = (n - self.lead) // len(self.pattern)
        self.rem = self.pattern[: (n - self.lead) % len(self.pattern)]

    # -- init ---------------------------------------------------------------
    def init(self, key):
        p = {}
        for i in range(self.lead):
            p[f"lead{i}"] = init_layer(
                jax.random.fold_in(key, 2000 + i), self.cfg, self.pattern[0],
                cross=self.cross, dense=True)
        for j, kind in enumerate(self.pattern):
            keys = jax.random.split(jax.random.fold_in(key, j), self.n_full)
            p[f"pos{j}"] = jax.vmap(
                lambda k: init_layer(k, self.cfg, kind, cross=self.cross))(keys)
        for i, kind in enumerate(self.rem):
            p[f"rem{i}"] = init_layer(
                jax.random.fold_in(key, 1000 + i), self.cfg, kind, cross=self.cross)
        return p

    def init_state(self, batch, max_len, dtype):
        if self.lead:
            raise NotImplementedError("decode through leading dense layers")
        st = {}
        n = self.n_full
        for j, kind in enumerate(self.pattern):
            one = init_layer_state(self.cfg, kind, batch, max_len, dtype)
            st[f"pos{j}"] = jax.tree.map(
                lambda a: jnp.tile(a[None], (n,) + (1,) * a.ndim), one)
        for i, kind in enumerate(self.rem):
            st[f"rem{i}"] = init_layer_state(self.cfg, kind, batch, max_len, dtype)
        return st

    # -- train / eval forward -------------------------------------------------
    def apply(self, params, x, positions, *, enc_states=None, causal=True,
              remat="none"):
        cfg, pattern = self.cfg, self.pattern

        def block(carry, block_params):
            x, aux = carry
            for j, kind in enumerate(pattern):
                x, a = apply_layer(block_params[f"pos{j}"], x, cfg, kind,
                                   positions, enc_states=enc_states,
                                   causal=causal, remat=remat)
                aux = aux + a
            return (x, aux), None

        def lead(p_, x_):
            return apply_layer(p_, x_, cfg, pattern[0], positions,
                               enc_states=enc_states, causal=causal,
                               remat=remat)

        if remat == "full":
            blockf, lead = jax.checkpoint(block), jax.checkpoint(lead)
        else:
            blockf = block
        aux = 0.0
        for i in range(self.lead):
            x, a = lead(params[f"lead{i}"], x)
            aux = aux + a
        scanned = {k: v for k, v in params.items() if k.startswith("pos")}
        if self.n_full and cfg.scan_blocks:
            (x, aux), _ = jax.lax.scan(blockf, (x, aux), scanned)
        elif self.n_full:
            carry = (x, aux)
            for i in range(self.n_full):
                carry, _ = blockf(carry, jax.tree.map(lambda a: a[i], scanned))
            x, aux = carry
        for i, kind in enumerate(self.rem):
            x, a = apply_layer(params[f"rem{i}"], x, cfg, kind, positions,
                               enc_states=enc_states, causal=causal, remat=remat)
            aux = aux + a
        return x, aux

    # -- prefill (forward + build decode state) -------------------------------
    def prefill(self, params, x, positions, state, *, enc_states=None):
        cfg, pattern = self.cfg, self.pattern

        def block(x, xs):
            block_params, block_state = xs
            new_states = {}
            for j, kind in enumerate(pattern):
                x, ns = apply_layer_prefill(
                    block_params[f"pos{j}"], x, cfg, kind, positions,
                    block_state[f"pos{j}"], enc_states=enc_states)
                new_states[f"pos{j}"] = ns
            return x, new_states

        scanned_p = {k: v for k, v in params.items() if k.startswith("pos")}
        scanned_s = {k: v for k, v in state.items() if k.startswith("pos")}
        new_state = dict(state)
        if self.n_full:
            x, ns = self._iterate(block, x, (scanned_p, scanned_s))
            new_state.update(ns)
        for i, kind in enumerate(self.rem):
            x, ns = apply_layer_prefill(
                params[f"rem{i}"], x, cfg, kind, positions,
                state[f"rem{i}"], enc_states=enc_states)
            new_state[f"rem{i}"] = ns
        return x, new_state

    def _iterate(self, block, x, xs):
        """scan or unrolled loop over the stacked block dim (see
        ModelConfig.scan_blocks)."""
        if self.cfg.scan_blocks:
            return jax.lax.scan(block, x, xs)
        ys = []
        for i in range(self.n_full):
            x, y = block(x, jax.tree.map(lambda a: a[i], xs))
            ys.append(y)
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *ys)
        return x, stacked

    # -- one-token decode ------------------------------------------------------
    def decode(self, params, x, pos, state, *, enc_states=None):
        cfg, pattern = self.cfg, self.pattern

        def block(x, xs):
            block_params, block_state = xs
            new_states = {}
            for j, kind in enumerate(pattern):
                x, ns = apply_layer_decode(
                    block_params[f"pos{j}"], x, cfg, kind, pos,
                    block_state[f"pos{j}"], enc_states=enc_states)
                new_states[f"pos{j}"] = ns
            return x, new_states

        scanned_p = {k: v for k, v in params.items() if k.startswith("pos")}
        scanned_s = {k: v for k, v in state.items() if k.startswith("pos")}
        new_state = dict(state)
        if self.n_full:
            x, ns = self._iterate(block, x, (scanned_p, scanned_s))
            new_state.update(ns)
        for i, kind in enumerate(self.rem):
            x, ns = apply_layer_decode(
                params[f"rem{i}"], x, cfg, kind, pos, state[f"rem{i}"],
                enc_states=enc_states)
            new_state[f"rem{i}"] = ns
        return x, new_state
