"""What every entry shares: Adam as the job's ``optimizer`` block states
it, jitted as ``adam_update`` (the name ``adam_device_ms`` reads), and
the readings ``bench/check.py`` compares, taken from the program's own
state in the benchmark's leaf names."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import reference
from repro.configs.base import TrainConfig
from repro.optim import adam


class TrainEntry:
    """Subclasses set ``self.init`` (seed key -> the program's params) and
    call ``super().__init__`` with the job and their layout's
    ``from_program``."""

    def __init__(self, job, key, from_program):
        opt = job["optimizer"]
        self.key, self.b1 = key, opt["b1"]
        tcfg = TrainConfig(
            global_batch=job["rows"], micro_batch=job["micro_batch"],
            seq_len=job["seq"], steps=opt["steps"],
            warmup_steps=opt["warmup_steps"],
            learning_rate=opt["learning_rate"],
            weight_decay=opt["weight_decay"], grad_clip=opt["grad_clip"])

        def adam_update(params, grads, state):
            return adam.update(params, grads, state, tcfg, b1=opt["b1"],
                               b2=opt["b2"], eps=opt["eps"])

        self.update = jax.jit(adam_update, donate_argnums=(0, 2))
        self.norms = jax.jit(lambda t: reference.leaf_norms(from_program(t)))
        self.diff_norms = jax.jit(lambda a, b: reference.leaf_norms(
            from_program(jax.tree.map(jnp.subtract, a, b))))

    def block(self):
        jax.block_until_ready(self.params)

    def first_grad_norms(self):
        """Per-leaf norms of the first clipped gradient, read from Adam's
        first moment after one update: m = (1 - b1) g."""
        return jax.tree.map(lambda n: n / (1.0 - self.b1),
                            self.norms(self.opt.m))

    def change_norms(self):
        """Per-leaf norms of the params' change since the seed's weights."""
        p0 = self.init(self.key)
        out = self.diff_norms(self.params, p0)
        del p0
        return out

    def finish(self):
        self.params = self.opt = None
