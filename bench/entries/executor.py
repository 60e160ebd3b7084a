"""Entry ``executor``: one training step is ``PipelineExecutor.step``
followed by the jitted ``optim.adam.update``, every stage on the default
device. This is the program's interpreter of a compiled ``plan.Schedule``:
one ``jax.vjp`` per F, the stash in the activation store, EVICT/LOAD as
store moves.
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

#: bench layer leaf -> (program sub-tree, key) inside one stacked layer
LAYER_KEYS = {"ln1_scale": ("norm1", "scale"), "ln2_scale": ("norm2", "scale"),
              "wq": ("mixer", "wq"), "wk": ("mixer", "wk"),
              "wv": ("mixer", "wv"), "wo": ("mixer", "wo"),
              "bq": ("mixer", "bq"), "bk": ("mixer", "bk"),
              "bv": ("mixer", "bv"), "wi": ("ffn", "wi"),
              "wg": ("ffn", "wg"), "w2": ("ffn", "wo")}


def to_program(t):
    """The benchmark's layout -> ``models.model``'s (a re-keying)."""
    layer = {}
    for k, a in t["layers"].items():
        sub, key = LAYER_KEYS[k]
        layer.setdefault(sub, {})[key] = a
    embed = {"table": t["embed"]}
    if "unembed" in t:
        embed["unembed"] = t["unembed"]
    return {"embed": embed, "blocks": {"pos0": layer},
            "final_norm": t["final_norm"]}


def from_program(p):
    """``models.model``'s layout -> the benchmark's."""
    layer = {k: p["blocks"]["pos0"][sub][key]
             for k, (sub, key) in LAYER_KEYS.items()
             if key in p["blocks"]["pos0"].get(sub, {})}
    out = {"embed": p["embed"]["table"], "layers": layer,
           "final_norm": p["final_norm"]}
    if "unembed" in p["embed"]:
        out["unembed"] = p["embed"]["unembed"]
    return out


class Entry(TrainEntry):
    def __init__(self, cfg, model, job, key, devices):
        if cfg.block_pattern != ("attn",) or cfg.moe is not None:
            raise ValueError("entry executor: uniform attention decoders only")
        super().__init__(job, key, from_program)
        sch = job["schedule"]
        self.cfg, self.job = cfg, job
        self.spec = ScheduleSpec(sch["kind"], sch["p"], sch["m"])
        self.ex = PipelineExecutor(cfg, spec=self.spec,
                                   micro_batch=job["micro_batch"],
                                   remat=job["remat"])
        self.init = jax.jit(lambda k: to_program(
            reference.init_params(model, k)))
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
