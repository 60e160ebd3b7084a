"""Compiled SPMD pipeline parallelism: shard_map + collective_permute.

This is the *lowering/scale* half of the pipeline story (the interpreter
in executor.py is the *memory-semantics* half — see DESIGN.md §5.3):

  * stages live on the ``stage`` mesh axis (the production mesh's "model"
    axis), activations flow stage->stage+1 through ``lax.ppermute``;
  * microbatches stream GPipe-style over m + p - 1 ticks inside one
    ``lax.scan`` => the HLO is O(1) in both depth and microbatch count;
  * per-tick stage compute is rematerialized (jax.checkpoint), bounding
    stash memory to tick-boundary states (XLA/GSPMD cannot express true
    MPMD 1F1B stash rotation — this is a documented platform adaptation);
  * ``bpipe_stash=True`` applies the BPipe eviction pattern to the saved
    tick-boundary activation: the autodiff residual is shipped to the
    paired stage after the forward tick and fetched back in the backward
    — two extra collective-permutes per tick whose bytes are visible to
    the roofline pass (kernels of the paper's Fig. 1 arrows).

Uniform stages required: num_layers % p == 0 (true for the paper's
GPT-3/LLaMA at p = 16, the Fig. 2 configuration).
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.bpipe import pair_adjacent_layout
from repro.models.blocks import apply_layer, init_layer
from repro.models.layers import (apply_norm, embed, init_embed, init_norm,
                                 unembed)


# ---------------------------------------------------------------------------
# Parameters: stage-stacked
# ---------------------------------------------------------------------------
def init_pipeline_params(key, cfg: ModelConfig, p: int):
    """Per-stage stacked layer params (leading dim p) + shared head/tail."""
    assert cfg.num_layers % p == 0, (cfg.num_layers, p)
    per = cfg.num_layers // p
    kinds = cfg.layer_kinds()
    assert all(k == kinds[0] for k in kinds) or per % len(cfg.block_pattern) == 0, \
        "stage boundaries must align with the block pattern"

    def init_stage(k):
        ks = jax.random.split(k, per)
        return [init_layer(ks[j], cfg, kinds[j]) for j in range(per)]

    keys = jax.random.split(key, p)
    stages = jax.vmap(init_stage)(keys)  # leaves: (p, ...)
    return {
        "stages": stages,
        "embed": init_embed(jax.random.fold_in(key, 1), cfg),
        "final_norm": init_norm(cfg),
    }


# ---------------------------------------------------------------------------
# BPipe remote stash (custom_vjp around the per-tick stage compute)
# ---------------------------------------------------------------------------
def _remote_remat(fn, perm_out, perm_back, axis):
    """Recompute-in-backward whose saved input lives on the BPipe partner.

    fwd: y = fn(params, x); residual = ppermute(x -> partner)
    bwd: x = ppermute(residual -> back); grads = vjp(fn)(g)
    """

    @jax.custom_vjp
    def wrapped(params, x):
        return fn(params, x)

    def fwd(params, x):
        y = fn(params, x)
        stash = jax.lax.ppermute(x, axis, perm_out)   # EVICT
        return y, (params, stash)

    def bwd(res, g):
        params, stash = res
        x = jax.lax.ppermute(stash, axis, perm_back)  # LOAD
        _, vjp_fn = jax.vjp(fn, params, x)
        return vjp_fn(g)

    wrapped.defvjp(fwd, bwd)
    return wrapped


def _bpipe_perms(p: int):
    """Device-level permutation pairs for the eviction hop. With the
    pair-adjacent layout stages sit so each (x, p-1-x) pair is 1 ICI hop
    apart; on the raw stage axis the permutation is stage->partner."""
    pairs = [(x, p - 1 - x) for x in range(p // 2)]
    perm_out = [(a, b) for a, b in pairs] + [(b, a) for a, b in pairs]
    if p % 2:
        mid = p // 2
        perm_out.append((mid, mid))
    return perm_out, perm_out  # involution: same permutation both ways


# ---------------------------------------------------------------------------
# The pipelined loss
# ---------------------------------------------------------------------------
def pipeline_loss_fn(cfg: ModelConfig, p: int, num_micro: int, *,
                     stage_axis: str = "model", data_axis="data",
                     bpipe_stash: bool = False, remat: bool = True):
    """Returns loss(params, batch) to be used under shard_map/jit.

    batch: tokens/labels (local_batch, s) already sharded over data axes.
    Must be called inside shard_map over (data_axis, stage_axis).
    """
    per = cfg.num_layers // p
    kinds = cfg.layer_kinds()

    def stage_compute(stage_params, x):
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        for j in range(per):
            # inside shard_map the stage-stacked leading dim is local (=1)
            lp = jax.tree.map(lambda a: a[0], stage_params[j])
            x, _ = apply_layer(lp, x, cfg, kinds[j], positions)
        return x

    perm_out, perm_back = _bpipe_perms(p)
    if bpipe_stash:
        stage_fn = _remote_remat(stage_compute, perm_out, perm_back, stage_axis)
    elif remat:
        stage_fn = jax.checkpoint(stage_compute)
    else:
        stage_fn = stage_compute

    shift = [(i, (i + 1) % p) for i in range(p)]

    def loss_fn(params, batch):
        idx = jax.lax.axis_index(stage_axis)
        tokens, labels = batch["tokens"], batch["labels"]
        bsz, s = tokens.shape
        assert bsz % num_micro == 0, (bsz, num_micro)
        mb = bsz // num_micro
        tok_mb = tokens.reshape(num_micro, mb, s)
        lbl_mb = labels.reshape(num_micro, mb, s)
        pad = jnp.zeros((p - 1, mb, s), tokens.dtype)
        tok_stream = jnp.concatenate([tok_mb, pad], 0)
        lbl_stream = jnp.concatenate(
            [jnp.full((p - 1, mb, s), -1, labels.dtype), lbl_mb], 0)

        vaxes0 = (stage_axis,) + (tuple(data_axis) if data_axis else ())
        state0 = jax.lax.pcast(
            jnp.zeros((mb, s, cfg.d_model), jnp.dtype(cfg.dtype)), vaxes0,
            to="varying")

        def tick(state, xs):
            tok_t, lbl_t = xs
            # stage 0 injects the next microbatch's embeddings
            inj = embed(params["embed"], tok_t, cfg)
            x = jnp.where(jnp.equal(idx, 0)[None, None, None], inj, state)
            y = stage_fn(params["stages"], x)

            # Microbatch loss, masked to the last stage. Uniform-SPMD: all
            # stages run the vocab matmul and multiply by an indicator.
            # (A lax.cond gate deadlocks here: replicated params used
            # inside a device-varying cond acquire pvary->psum transposes
            # that only the true-branch devices execute. The extra FLOPs
            # are netted out analytically in the roofline pass.)
            xl = apply_norm(params["final_norm"], y, cfg.norm_eps)
            logits = unembed(params["embed"], xl, cfg)
            mask = (lbl_t >= 0).astype(jnp.float32)
            lbl = jnp.maximum(lbl_t, 0)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            nll = -jnp.take_along_axis(logp, lbl[..., None], -1)[..., 0]
            mb_loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
            loss_t = mb_loss * jnp.equal(idx, p - 1).astype(jnp.float32)
            state = jax.lax.ppermute(y, stage_axis, shift)
            return state, loss_t

        _, losses = jax.lax.scan(tick, state0, (tok_stream, lbl_stream))
        total = jnp.sum(losses) / num_micro
        total = jax.lax.psum(total, stage_axis)
        if data_axis is not None:
            total = jax.lax.pmean(total, data_axis)
        return total

    return loss_fn


def make_spmd_train_loss(cfg: ModelConfig, mesh, p: int, num_micro: int,
                         *, bpipe_stash: bool = False):
    """shard_map-wrapped pipeline loss on the production mesh: the "model"
    axis carries stages, remaining axes carry data."""
    data_axes = tuple(a for a in mesh.axis_names if a != "model")
    inner = pipeline_loss_fn(cfg, p, num_micro, stage_axis="model",
                             data_axis=data_axes, bpipe_stash=bpipe_stash)

    def loss(params, batch):
        in_specs = (
            {"stages": jax.tree.map(lambda _: P("model"),
                                    params["stages"]),
             "embed": jax.tree.map(lambda _: P(), params["embed"]),
             "final_norm": jax.tree.map(lambda _: P(), params["final_norm"])},
            {"tokens": P(data_axes), "labels": P(data_axes)},
        )
        f = jax.shard_map(inner, mesh=mesh, in_specs=in_specs, out_specs=P())
        return f(params, batch)

    return loss
