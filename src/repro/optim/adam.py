"""Adam with weight decay, global-norm clipping, warmup+cosine schedule.

Mixed precision per the paper's setup: master params and both moments in
fp32 (the models cast weights to bf16 at use — "cast-on-read"), gradients
arrive fp32 from the fp32 loss.

Weight decay applies by leaf, to the weight matrices named in ``decayed``
(the last key of a leaf's path; ``DECAYED`` by default): never to norm
scales, biases or a router's selection bias, stacked or not.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Collection

import jax
import jax.numpy as jnp

from repro.configs.base import TrainConfig


#: The program's weight matrices (``models/``): embedding table and head;
#: attention, MLA and xLSTM projections; FFN, expert and router matrices;
#: RG-LRU projections, gates and conv taps; xLSTM recurrent matrices.
DECAYED = frozenset({"table", "unembed", "wq", "wk", "wv", "wo", "wkv_a",
                     "wkv_b", "wif", "wog", "w", "r", "wi", "wg", "router",
                     "in_x", "in_g", "out", "wa", "wx", "conv_w"})


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class AdamState:
    step: jnp.ndarray
    m: Any
    v: Any


def init(params) -> AdamState:
    zeros = lambda t: jax.tree.map(
        lambda a: jnp.zeros(a.shape, jnp.float32), t)
    return AdamState(step=jnp.zeros((), jnp.int32), m=zeros(params), v=zeros(params))


def lr_schedule(tcfg: TrainConfig, step):
    warm = jnp.minimum(1.0, (step + 1) / max(tcfg.warmup_steps, 1))
    prog = jnp.clip((step - tcfg.warmup_steps)
                    / max(tcfg.steps - tcfg.warmup_steps, 1), 0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
    return tcfg.learning_rate * warm * (0.1 + 0.9 * cos)


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(a.astype(jnp.float32)))
                        for a in jax.tree.leaves(tree)))


def update(params, grads, state: AdamState, tcfg: TrainConfig,
           b1=0.9, b2=0.95, eps=1e-8,
           decayed: Collection[str] = DECAYED):
    """Returns (new_params, new_state, metrics). Weight decay applies to
    the leaves whose last key is in ``decayed``."""
    step = state.step + 1
    gn = global_norm(grads)
    clip = jnp.minimum(1.0, tcfg.grad_clip / (gn + 1e-9)) if tcfg.grad_clip else 1.0
    grads = jax.tree.map(lambda g: g.astype(jnp.float32) * clip, grads)
    m = jax.tree.map(lambda mu, g: b1 * mu + (1 - b1) * g, state.m, grads)
    v = jax.tree.map(lambda nu, g: b2 * nu + (1 - b2) * g * g, state.v, grads)
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)
    lr = lr_schedule(tcfg, state.step)

    def upd(path, p, mu, nu):
        d = (mu / bc1) / (jnp.sqrt(nu / bc2) + eps)
        if tcfg.weight_decay and _key(path[-1]) in decayed:
            d = d + tcfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * d).astype(p.dtype)

    new_params = jax.tree_util.tree_map_with_path(upd, params, m, v)
    return new_params, AdamState(step=step, m=m, v=v), {
        "grad_norm": gn, "lr": lr}


def _key(k) -> str:
    return str(getattr(k, "key", getattr(k, "name", getattr(k, "idx", k))))
