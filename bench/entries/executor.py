"""Entry ``executor``: one training step is ``PipelineExecutor.step``
followed by the jitted ``optim.adam.update``, every stage on the default
device. This is the program's interpreter of a compiled ``plan.Schedule``:
one ``jax.vjp`` per F, the stash in the activation store, EVICT/LOAD as
store moves. The weights' layout is the cell's architecture module's
(``to_program``, ``from_program``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import reference
from bench.entries.common import TrainEntry
from repro.core import simulator
from repro.core.notation import Notation
from repro.core.plan import ScheduleSpec
from repro.optim import adam
from repro.pipeline import PipelineExecutor
from repro.planner.rank import AnalyticCostModel


class Entry(TrainEntry):
    def __init__(self, cfg, arch, model, job, key, devices):
        super().__init__(job, key, lambda p: arch.from_program(p, cfg))
        sch = job["schedule"]
        self.cfg, self.job = cfg, job
        self.spec = ScheduleSpec(sch["kind"], sch["p"], sch["m"])
        self.ex = PipelineExecutor(cfg, spec=self.spec,
                                   micro_batch=job["micro_batch"],
                                   remat=job["remat"])
        self.init = jax.jit(lambda k: arch.to_program(
            reference.init_params(arch, model, k), cfg))
        self.stats = None

    def start(self):
        self.params = self.init(self.key)
        self.opt = jax.jit(adam.init)(self.params)

    def put(self, batch):
        return {k: jnp.asarray(v) for k, v in batch.items()}

    def step(self, batch, span):
        with span("executor.step"):
            r = self.ex.step(self.params, batch)
        with span("adam.update"):
            self.params, self.opt, _ = self.update(self.params, r.grads,
                                                   self.opt)
        self.stats = r.stats
        return r.loss

    def info(self):
        st = self.stats
        yield (f"store: evictions {st.evictions} loads {st.loads} "
               f"peak units/stage {dict(st.peak_local)} "
               f"bytes moved {st.bytes_moved:.0f}")
        yield f"spec: {self.spec.label()}"

    def planner_step_s(self, peak_flops):
        """The planner's predicted step time for this spec: the analytic
        stage cost at the chip's bf16 peak, priced by the simulator."""
        cfg, job = self.cfg, self.job
        n = Notation(a=cfg.num_heads, b=job["micro_batch"], h=cfg.d_model,
                     l=cfg.num_layers, s=job["seq"], v=cfg.vocab_size,
                     B=job["rows"], p=self.spec.p, t=1)
        attention = {"flash": "flash", "attn": "recompute"}.get(
            job["remat"], "none")
        t = AnalyticCostModel(cfg, peak_per_chip=peak_flops).stage_T(
            n, attention)
        res = simulator.simulate(simulator.SimConfig(
            spec=self.spec, Tf=t / 3.0, Tb=2.0 * t / 3.0))
        return res.makespan
