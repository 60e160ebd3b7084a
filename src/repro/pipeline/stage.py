"""Split a full model into pipeline stages (Megatron-style layer ranges).

``split_params`` regroups the PatternStack's stacked parameters into
per-stage, per-layer params; ``merge_stage_grads`` restacks gradients into
the original structure so the optimizer is pipeline-agnostic. Tied
embeddings are replicated onto the first and last stage and their grads
summed at merge (Megatron ties them with an all-reduce the same way).

All functions are written over *virtual* stages: for interleaved
schedules with v chunks per device, pass ``p * v`` as the stage count and
index with ``virtual_stage = chunk * p + device`` — chunk c on device s
then holds the layer slice of virtual stage c*p + s, the first virtual
stage embeds, and the last computes the loss. ``StageSplitter`` hoists
the assignment/PatternStack bookkeeping so executors don't rebuild it
every step.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.blocks import (PatternStack, apply_layer,
                                 apply_layer_sliced)
from repro.models.layers import apply_norm, embed, unembed


def layer_assignment(cfg: ModelConfig, p: int) -> List[List[int]]:
    """Contiguous layer ranges per stage (uniform; remainder to late stages,
    which hold fewer in-flight activations under 1F1B)."""
    n = cfg.num_layers
    base, extra = divmod(n, p)
    sizes = [base + (1 if i >= p - extra else 0) for i in range(p)]
    out, ℓ = [], 0
    for s in sizes:
        out.append(list(range(ℓ, ℓ + s)))
        ℓ += s
    return out


class StageSplitter:
    """Per-(cfg, n_stages) split/merge with the layer assignment and
    PatternStack bookkeeping computed once (executors hold one of these
    across steps instead of rebuilding it per call)."""

    def __init__(self, cfg: ModelConfig, n_stages: int):
        self.cfg, self.n = cfg, n_stages
        self.assign = layer_assignment(cfg, n_stages)
        self.stack = PatternStack(cfg)

    def _layer_params(self, params, ℓ: int):
        k, lead = len(self.stack.pattern), self.stack.lead
        if ℓ < lead:
            return params["blocks"][f"lead{ℓ}"]
        blk, j = divmod(ℓ - lead, k)
        if blk < self.stack.n_full:
            return jax.tree.map(lambda a: a[blk], params["blocks"][f"pos{j}"])
        return params["blocks"][f"rem{ℓ - lead - self.stack.n_full * k}"]

    def split(self, params) -> List[Dict[str, Any]]:
        stages = []
        for i, layers in enumerate(self.assign):
            sp: Dict[str, Any] = {
                "layers": [self._layer_params(params, ℓ) for ℓ in layers]}
            if i == 0:
                sp["embed"] = params["embed"]
            if i == self.n - 1:
                sp["final_norm"] = params["final_norm"]
                # unembed weights (tied table or separate matrix)
                sp["unembed"] = params["embed"]
            stages.append(sp)
        return stages

    def merge(self, stage_grads: List[Dict[str, Any]]):
        """Restack per-stage layer grads into full-model param structure."""
        k, lead = len(self.stack.pattern), self.stack.lead
        per_layer = {}
        for sg, layers in zip(stage_grads, self.assign):
            for local, ℓ in enumerate(layers):
                per_layer[ℓ] = sg["layers"][local]
        blocks: Dict[str, Any] = {f"lead{i}": per_layer[i]
                                  for i in range(lead)}
        for j in range(k):
            rows = [per_layer[lead + blk * k + j]
                    for blk in range(self.stack.n_full)]
            blocks[f"pos{j}"] = jax.tree.map(lambda *a: jnp.stack(a), *rows)
        for i in range(len(self.stack.rem)):
            blocks[f"rem{i}"] = per_layer[lead + self.stack.n_full * k + i]
        embed_grad = stage_grads[0]["embed"]
        tail = stage_grads[-1]
        embed_grad = jax.tree.map(jnp.add, embed_grad, tail["unembed"])
        return {"embed": embed_grad, "blocks": blocks,
                "final_norm": tail["final_norm"]}


def split_params(params, cfg: ModelConfig, p: int) -> List[Dict[str, Any]]:
    return StageSplitter(cfg, p).split(params)


def merge_stage_grads(stage_grads: List[Dict[str, Any]], cfg: ModelConfig,
                      p: int, params_template=None):
    return StageSplitter(cfg, p).merge(stage_grads)


# ---------------------------------------------------------------------------
# Stage forward functions
# ---------------------------------------------------------------------------
def make_stage_fn(cfg: ModelConfig, p: int, stage: int, remat: str = "none"):
    """Returns f(stage_params, x_or_tokens, batch) -> activation or loss.

    Stage 0 consumes batch tokens (embeds); the last stage returns the
    scalar mean loss for the microbatch. MoE aux-loss is folded in.
    """
    assign = layer_assignment(cfg, p)
    kinds = cfg.layer_kinds()
    layers = assign[stage]
    first, last = stage == 0, stage == p - 1

    def fn(sp, carry, batch):
        """carry = (activation, running_aux). Stage 0 builds it from tokens;
        the last stage collapses it to the scalar microbatch loss."""
        if first:
            x = embed(sp["embed"], batch["tokens"], cfg)
            aux = jnp.zeros((), jnp.float32)
        else:
            x, aux = carry
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        for local, ℓ in enumerate(layers):
            x, a = apply_layer(sp["layers"][local], x, cfg, kinds[ℓ],
                               positions, remat=remat)
            aux = aux + a
        if not last:
            return x, aux
        x = apply_norm(sp["final_norm"], x, cfg.norm_eps)
        logits = unembed(sp["unembed"], x, cfg)
        labels = batch["labels"]
        mask = (labels >= 0).astype(jnp.float32)
        lbl = jnp.maximum(labels, 0)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, lbl[..., None], axis=-1)[..., 0]
        loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return loss + aux

    return fn


def make_sliced_stage_fn(cfg: ModelConfig, p: int, stage: int,
                         remat: str = "none"):
    """Sequence-sliced stage forward (``ScheduleSpec.seq_chunks`` > 1,
    docs/longcontext.md). Returns

        f(sp, carry, kv_prefix, batch) -> (primary, kv_own)

    where ``batch`` holds this slice's tokens/labels plus ``"offset"``
    (the slice's global start position, an int32 scalar), ``kv_prefix``
    is one (k, v) pair per local layer covering global positions
    [0, offset) — zero-length for slice 0 — and ``kv_own`` is the
    slice's own post-RoPE KV the executor retains for later slices.

    ``primary`` is (activation, aux) on interior stages and
    (nll_sum, aux) on the last stage — the nll sum is UN-normalized;
    the executor divides by the microbatch's total valid-token count so
    the summed slice losses equal the unchunked stage loss.
    """
    assign = layer_assignment(cfg, p)
    kinds = cfg.layer_kinds()
    layers = assign[stage]
    first, last = stage == 0, stage == p - 1

    def fn(sp, carry, kv_prefix, batch):
        if first:
            x = embed(sp["embed"], batch["tokens"], cfg)
            aux = jnp.zeros((), jnp.float32)
        else:
            x, aux = carry
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(
            batch["offset"].astype(jnp.int32)
            + jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        kv_own = []
        for local, ℓ in enumerate(layers):
            x, a, kv = apply_layer_sliced(
                sp["layers"][local], x, cfg, kinds[ℓ], positions,
                kv_prefix[local], remat=remat)
            aux = aux + a
            kv_own.append(kv)
        kv_own = tuple(kv_own)
        if not last:
            return (x, aux), kv_own
        x = apply_norm(sp["final_norm"], x, cfg.norm_eps)
        logits = unembed(sp["unembed"], x, cfg)
        labels = batch["labels"]
        mask = (labels >= 0).astype(jnp.float32)
        lbl = jnp.maximum(labels, 0)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, lbl[..., None], axis=-1)[..., 0]
        return (jnp.sum(nll * mask), aux), kv_own

    return fn
