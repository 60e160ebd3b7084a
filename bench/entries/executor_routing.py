"""Entry ``executor_routing``: the ``executor`` entry (``PipelineExecutor.step``
then the jitted Adam update) for a model with routed experts, with a
routing counter read once, in set-up, before the first step: the
architecture module's ``held_load`` over the first batch on the seed's
weights, in the reference's float32, one micro-batch at a time. It gives
the share of the picks that land on the experts this layer holds, and the
largest and mean number of picks a held expert gets in one micro-batch of
one MoE layer. Nothing is added to a step, so the measured window runs the
same executables as the ``executor`` entry.

The counts are printed by ``info()`` and left in the job under
``"routing"``, where the ``moe_expert_roofline`` reducer reads the held
picks its work is counted at.
"""
from __future__ import annotations

import jax

from bench import reference
from bench.entries import executor


class Entry(executor.Entry):
    def __init__(self, cfg, arch, model, job, key, devices):
        super().__init__(cfg, arch, model, job, key, devices)
        self.arch, self.model = arch, model
        job.pop("routing", None)    # an earlier seed's, in bench/control.py

    def step(self, batch, span):
        if "routing" not in self.job:
            self.job["routing"] = self._routing(batch["tokens"])
        return super().step(batch, span)

    def _routing(self, tokens):
        arch, model, mb = self.arch, self.model, self.job["micro_batch"]
        weights = jax.jit(lambda k: reference.init_params(arch, model, k))(
            self.key)
        count = jax.jit(lambda t, x: arch.held_load(model, t, x))
        rows = [jax.device_get(count(weights, tokens[i:i + mb]))
                for i in range(0, tokens.shape[0], mb)]
        del weights
        return {"held_picks": sum(int(r["held_picks"]) for r in rows),
                "picks": sum(int(r["picks"]) for r in rows),
                "max_load": max(int(r["max_load"]) for r in rows),
                "mean_load": sum(float(r["mean_load"]) for r in rows)
                / len(rows)}

    def info(self):
        yield from super().info()
        r, e = self.job["routing"], self.model["moe"]
        yield (f"routing (first step, {e['held']} of {e['num_experts']} "
               f"experts held): {r['held_picks']} of {r['picks']} picks on "
               f"held experts ({100.0 * r['held_picks'] / r['picks']:.2f}%); "
               f"picks a held expert gets in one micro-batch of one layer: "
               f"max {r['max_load']}, mean {r['mean_load']:.2f}")
