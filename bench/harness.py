"""The benchmark's run: set-up, measured window, trace, and the check.

A cell is one entry of ``BENCHMARK.json``'s ``workloads``. Everything that
belongs to it is found by name:

  bench/configs/<file named in configs[].file>  the model's sizes, and
                                 in ``reference`` its architecture
  bench/models/<reference>.py    the architecture's reference, weight
                                 layout and FLOP count
  bench/traffic/<traffic>.json   the job: batch, sequence, schedule,
                                 optimizer, and which entry runs it
  bench/entries/<entry>.py       the timed path (class ``Entry``)
  bench/limits/<cell>.json       the limits of the numbers compared
  bench/metrics/<metric>.py      one reducer per per-layer metric

Set-up makes the weights from the seed, then drives the entry's own step
through the job's first ``check_steps`` steps (which also warms up every
shape) and records what ``bench/check.py`` compares. The window then runs
whole steps for ``--seconds`` (or, traced, ``trace_steps`` steps) with no
host read of any result, and ends in ``block_until_ready``. After the
window the program's state is freed and the float32 reference repeats the
first steps.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import pathlib
import shutil
import tempfile
import time
import types
import typing
from typing import Callable, Dict, List, Optional

import jax

from bench import check, flops, reference, trace as trace_mod, traffic

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
HOST_SPANS = ("window", "batch", "executor.step", "adam.update",
              "loss_read")
LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def load_json(path) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict        # the configuration file
    job: Dict           # the traffic file
    limits: Dict        # name -> limit of each compared number
    per_layer: List[Dict]
    end_to_end: List[Dict]
    arch: types.ModuleType  # the configuration's ``bench/models`` module


def find_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def mine(ms):
        return [m for m in ms if name in m.get("workloads", [name])]

    config = load_json(root / cfg["file"])
    return Cell(name=name, chips=w["chips"], config=config,
                job=load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
                limits=load_json(root / "bench" / "limits" / f"{name}.json")["limits"],
                per_layer=mine(bench["per_layer"]),
                end_to_end=mine(bench["end_to_end"]),
                arch=load_arch(config["reference"]))


def load_arch(name: str) -> types.ModuleType:
    return importlib.import_module(f"bench.models.{name}")


def model_config(config: Dict, job: Dict):
    """The program's ``ModelConfig``: the registered one with the
    configuration file's sizes and the job's attention implementation. A
    nested block (``moe``) replaces the named keys of the registered
    block, or builds the field's own type where that is None."""
    from repro.configs import get_config
    base = get_config(config["base"])
    fields = {f.name for f in dataclasses.fields(base)}
    kw = {k: _field_value(base, k, v)
          for k, v in config["model"].items() if k in fields}
    return dataclasses.replace(base, attn_impl=job["attn_impl"], **kw)


def _field_value(base, name: str, v):
    if isinstance(v, list):
        return tuple(v)
    if not isinstance(v, dict):
        return v
    registered = getattr(base, name)
    if registered is not None:
        return dataclasses.replace(registered, **v)
    hint = typing.get_type_hints(type(base))[name]
    (cls,) = [t for t in typing.get_args(hint) or (hint,)
              if dataclasses.is_dataclass(t)]
    return cls(**v)


def _load_file(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_entry(name: str):
    return importlib.import_module(f"bench.entries.{name}")


class CompileCounter:
    """Counts executables lowered and compiled while ``active``."""

    _registered: Optional["CompileCounter"] = None

    def __init__(self):
        self.active = False
        self.lowered = 0
        self.compiled = 0

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._registered is None:
            c = cls._registered = cls()
            jax.monitoring.register_event_duration_secs_listener(c._on)
        return cls._registered

    def _on(self, event, _secs, **_kw):
        if self.active:
            self.lowered += event == LOWERING
            self.compiled += event == BACKEND_COMPILE

    @contextlib.contextmanager
    def counting(self):
        self.lowered = self.compiled = 0
        self.active = True
        try:
            yield self
        finally:
            self.active = False


def span(name: str):
    return jax.profiler.TraceAnnotation(name)


def peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def start(cell: Cell, seed: int, devices, log: Callable[[str], None] = print,
          peaks: Optional[Dict] = None):
    """Set-up: the entry built from the seed's weights and driven through
    the job's first ``check_steps`` steps by its own step call (which also
    warms up every shape). Returns the entry, the program's readings for
    ``bench/check.py``, the batch generator and the program's config."""
    job, model = cell.job, cell.config["model"]
    cfg = model_config(cell.config, job)
    rows, seq = job["rows"], job["seq"]
    key = reference.seed_key(seed)

    def batch_at(step):
        return traffic.make_batch(model["vocab_size"], rows, seq, seed, step,
                                  job["zipf_a"])

    log(f"cell {cell.name}: {cfg.name} layers {cfg.num_layers} d_model "
        f"{cfg.d_model} heads {cfg.num_heads}/{cfg.num_kv_heads} hd "
        f"{cfg.head_dim} d_ff {cfg.d_ff} vocab {cfg.vocab_size} tied "
        f"{cfg.tie_embeddings} "
        f"attn {cfg.attn_impl}; batch {rows} x {seq} tokens, job "
        f"{json.dumps({k: job[k] for k in ('entry', 'schedule', 'micro_batch', 'remat')})}")
    entry = load_entry(job["entry"]).Entry(cfg, cell.arch, model, job, key,
                                           devices)
    entry.start()
    losses = []
    for step in range(job["check_steps"]):
        with span("batch"):
            b = entry.put(batch_at(step))
        losses.append(entry.step(b, span))
        if step == 0:
            first = entry.first_grad_norms()
    change = entry.change_norms()
    kinds = cell.arch.layer_kinds(model)
    prog = {"loss": [float(x) for x in losses],
            "grad": reference.per_leaf(first, kinds),
            "change": reference.per_leaf(change, kinds)}
    for line in entry.info():
        log(line)
    if peaks is not None and hasattr(entry, "planner_step_s"):
        log(f"planner predicted step time: "
            f"{entry.planner_step_s(peaks['bf16_flops_per_s']):.6f} s "
            f"(analytic stage cost at the bf16 peak, simulated schedule)")
    return entry, prog, batch_at, cfg


def metric_ctx(cell: Cell, cfg, tr, lo: int, hi: int, ids, steps: int,
               peaks: Optional[Dict]) -> Dict:
    """What a ``bench/metrics`` reducer reads: the trace and its window,
    the chips' ids, the steps traced, the program's config, the job, the
    chip's peaks, the architecture module (``model``) and the
    configuration's sizes (``sizes``)."""
    return {"trace": tr, "lo": lo, "hi": hi, "devices": ids, "steps": steps,
            "cfg": cfg, "job": cell.job, "peaks": peaks, "flops": flops,
            "trace_mod": trace_mod, "model": cell.arch,
            "sizes": cell.config["model"]}


def run(cell: Cell, seed: int, seconds: float, traced: bool, *,
        t0: float, devices, log: Callable[[str], None] = print,
        peaks: Optional[Dict] = None) -> Dict:
    """One run of ``cell``; returns the result object (the last line)."""
    job, model = cell.job, cell.config["model"]
    tokens_per_step = job["rows"] * job["seq"]
    entry, prog, batch_at, cfg = start(cell, seed, devices, log, peaks)
    # What set-up made (executables, traces, caches) lives through the
    # window: keep it out of the window's full collections, whose count
    # and length otherwise vary from run to run of the host-bound step.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t0
    log(f"set-up: {setup_s:.3f} s; first losses {prog['loss']}")

    # --- the measured window
    counter = CompileCounter.get()
    step = job["check_steps"]
    window_losses = []
    result: Dict = {}
    with counter.counting():
        if not traced:
            t_start = time.perf_counter()
            while True:
                with span("batch"):
                    b = entry.put(batch_at(step))
                window_losses.append(entry.step(b, span))
                step += 1
                if time.perf_counter() - t_start >= seconds:
                    break
            with span("loss_read"):
                entry.block()
            window_s = time.perf_counter() - t_start
        else:
            log_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            with span("window"):
                for _ in range(job["trace_steps"]):
                    with span("batch"):
                        b = entry.put(batch_at(step))
                    window_losses.append(entry.step(b, span))
                    step += 1
                with span("loss_read"):
                    entry.block()
            jax.profiler.stop_trace()
    memory_peak = peak_bytes(devices)
    n_steps = len(window_losses)
    window_vals = [float(x) for x in window_losses]
    failed = sum(not math.isfinite(x) for x in window_vals)
    log(f"window: {n_steps} steps; compiles inside it: {counter.lowered} "
        f"lowered, {counter.compiled} compiled; losses {window_vals}")
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    if not traced:
        metrics = {"tokens_per_s": n_steps * tokens_per_step / window_s,
                   "peak_hbm_gib": memory_peak / 2**30, "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": metrics[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    else:
        tr = trace_mod.load(log_dir, HOST_SPANS)
        shutil.rmtree(log_dir, ignore_errors=True)
        (win,) = tr.spans("window")
        lo, hi = win.start, win.end
        ids = [d.id for d in devices]
        busy = [trace_mod.length(trace_mod.busy(tr, i, lo, hi)) / 1e9
                for i in ids]
        device.update(busy_s=sum(busy) / len(busy), window_s=(hi - lo) / 1e9)
        ctx = metric_ctx(cell, cfg, tr, lo, hi, ids, n_steps, peaks)
        result["metrics"] = {}
        for m in cell.per_layer:
            v = _load_file("metrics", m["name"]).read(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": trace_mod.top_ops(tr, ids, lo, hi),
            "idle_gaps": trace_mod.idle_gaps(tr, ids[0], lo, hi,
                                             HOST_SPANS[1:])}
        totals: Dict[str, float] = {}
        for name, s in trace_mod.idle_gaps(tr, ids[0], lo, hi,
                                           HOST_SPANS[1:], n=None):
            totals[name] = totals.get(name, 0.0) + s
        log(f"idle seconds of chip {ids[0]} by host span: {totals}")
    entry.finish()
    del entry, window_losses
    gc.unfreeze()
    gc.collect()

    # --- the check, after the program's state is freed
    t_ref = time.perf_counter()
    ref = reference.train_readings(
        cell.arch, model, job["optimizer"],
        [batch_at(s) for s in range(job["check_steps"])],
        reference.seed_key(seed), devices=devices)
    numbers = check.gaps(prog, ref)
    numbers["window_compiles"] = counter.lowered + counter.compiled
    ok, rows_ = check.judge(numbers, cell.limits)
    log(f"reference: {time.perf_counter() - t_ref:.3f} s; losses "
        f"{ref['loss']}; worst grad leaf {numbers['grad_leaf']}, worst "
        f"update leaf {numbers['update_leaf']}, leaves left out of the "
        f"update {numbers['leaves_left_out']}")
    result.update(correct=ok, attempted=n_steps, failed=failed, device=device)
    result["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in rows_}
    return {k: result[k] for k in ("correct", "attempted", "failed", "metrics",
                                   "device", *(("breakdown",) if traced else ()),
                                   "checks")}
