"""Top-level model: embeddings -> PatternStack -> norm -> logits.

Covers all assigned families:
  * decoder-only LMs (dense / MoE / SSM / hybrid),
  * encoder-decoder (whisper: stub audio-frame embeddings -> encoder,
    tokens -> decoder with cross-attention),
  * VLM (stub vision patch embeddings prepended to the token stream).

API:
  init_params(key, cfg)
  forward(params, batch, cfg, remat=...) -> (logits, aux_loss)
  loss_fn(params, batch, cfg, remat=...) -> (loss, metrics)
  init_decode_state(cfg, batch, max_len)
  prefill(params, batch, cfg, state) -> (logits_last, state)
  decode_step(params, token, pos, state, cfg, enc_states=None)
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, ModelConfig
from repro.models.blocks import PatternStack
from repro.models.layers import cdtype, embed, init_embed, init_norm, apply_norm, unembed

ENCODER_FRAMES = 1500  # whisper-style fixed encoder length (stub frontend)


def _stacks(cfg: ModelConfig):
    dec = PatternStack(cfg, cross=cfg.is_encdec)
    enc = None
    if cfg.is_encdec:
        enc = PatternStack(cfg, num_layers=cfg.encoder_layers, pattern=(ATTN,))
    return dec, enc


def init_params(key, cfg: ModelConfig):
    dec, enc = _stacks(cfg)
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {
        "embed": init_embed(ks[0], cfg),
        "blocks": dec.init(ks[1]),
        "final_norm": init_norm(cfg),
    }
    if enc is not None:
        p["encoder"] = {"blocks": enc.init(ks[2]), "norm": init_norm(cfg)}
    return p


def encode(params, enc_embeds, cfg):
    """Stub-frontend encoder: enc_embeds (b, frames, d) are precomputed
    frame/patch embeddings (the assignment's carve-out)."""
    _, enc = _stacks(cfg)
    x = enc_embeds.astype(cdtype(cfg))
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x, _ = enc.apply(params["encoder"]["blocks"], x, positions, causal=False)
    return apply_norm(params["encoder"]["norm"], x, cfg.norm_eps)


def _embed_inputs(params, batch, cfg):
    """Token (+ optional prefix) embedding. Returns (x, positions, n_prefix)."""
    tokens = batch["tokens"]
    x = embed(params["embed"], tokens, cfg)
    n_prefix = 0
    if cfg.frontend == "vision" and "prefix_embeds" in batch:
        pre = batch["prefix_embeds"].astype(x.dtype)
        n_prefix = pre.shape[1]
        x = jnp.concatenate([pre, x], axis=1)
    b, s = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    return x, positions, n_prefix


def forward(params, batch, cfg: ModelConfig, *, remat="none"):
    """batch: {tokens (b, s) [, prefix_embeds (b, n, d), enc_embeds]}.
    Returns (logits over token positions, moe aux loss)."""
    dec, _ = _stacks(cfg)
    enc_states = None
    if cfg.is_encdec:
        enc_states = encode(params, batch["enc_embeds"], cfg)
    x, positions, n_prefix = _embed_inputs(params, batch, cfg)
    x, aux = dec.apply(params["blocks"], x, positions,
                       enc_states=enc_states, remat=remat)
    x = apply_norm(params["final_norm"], x, cfg.norm_eps)
    if n_prefix:
        x = x[:, n_prefix:]
    logits = unembed(params["embed"], x, cfg)
    return logits, aux


def loss_fn(params, batch, cfg: ModelConfig, *, remat="none"):
    """Next-token cross-entropy in fp32 + MoE aux. labels==-1 is masked.

    Two implementations (EXPERIMENTS.md §Perf lever 1):
      baseline: log_softmax + take_along_axis — the gather over the
        vocab-sharded axis makes GSPMD all-gather the fp32 logits;
      fused (cfg.fused_xent): logsumexp + masked-reduce pick — every
        reduction is over the sharded vocab dim, so the (b, s, v) tensor
        never crosses devices and never materializes gathered.
    """
    logits, aux = forward(params, batch, cfg, remat=remat)
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    labels = jnp.maximum(labels, 0)
    # This fp32 (b, s/t, v) cast is the last-stage memory spike
    # memory_model.logits_bytes charges (docs/memory.md "Vocab
    # accounting") — at 151k vocab it rivals a whole stage's stash.
    lf = logits.astype(jnp.float32)
    if cfg.fused_xent:
        lse = jax.nn.logsumexp(lf, axis=-1)
        vocab_iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape,
                                              lf.ndim - 1)
        picked = jnp.sum(jnp.where(vocab_iota == labels[..., None], lf, 0.0),
                         axis=-1)
        nll = lse - picked
    else:
        logp = jax.nn.log_softmax(lf, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    total = loss + aux
    return total, {"loss": loss, "aux": aux}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------
def init_decode_state(cfg: ModelConfig, batch: int, max_len: int):
    dec, _ = _stacks(cfg)
    return dec.init_state(batch, max_len, cdtype(cfg))


def prefill(params, batch, cfg: ModelConfig, state):
    """Run the full prompt, fill decode state, return last-position logits."""
    dec, _ = _stacks(cfg)
    enc_states = None
    if cfg.is_encdec:
        enc_states = encode(params, batch["enc_embeds"], cfg)
    x, positions, n_prefix = _embed_inputs(params, batch, cfg)
    x, state = dec.prefill(params["blocks"], x, positions, state,
                           enc_states=enc_states)
    x = apply_norm(params["final_norm"], x[:, -1:], cfg.norm_eps)
    logits = unembed(params["embed"], x, cfg)[:, 0]
    return logits, state, enc_states


def decode_step(params, token, pos, state, cfg: ModelConfig, enc_states=None):
    """token: (b,) int32; pos: scalar int32 (position being written)."""
    x = embed(params["embed"], token[:, None], cfg)
    dec, _ = _stacks(cfg)
    x, state = dec.decode(params["blocks"], x, pos, state,
                          enc_states=enc_states)
    x = apply_norm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params["embed"], x, cfg)[:, 0]
    return logits, state
