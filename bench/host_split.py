#!/usr/bin/env python3
"""Where a cell's host time and the chip's idle time go, by the program's
``pipeline.*`` profiler spans (docs/observability.md "On the chip"):

    python3 bench/host_split.py --workload <cell> --seed <n> [--steps K]

Set-up as ``bench/run.py`` does it (``harness.start``), then three windows
of K whole steps each (K defaults to the job's ``trace_steps``): untraced,
under the profiler, untraced again. From the traced window it prints the
per-step host time of each part of ``PipelineExecutor.step`` (the
``bench/metrics`` reducers that declare ``SPANS``, plus the move spans)
beside ``executor_host_ms``, the host time and count of each
``pipeline.*`` span, the chip's idle seconds by innermost
``pipeline.*`` span and by the benchmark's host span, and the chip's
``custom-call`` operations by instruction name (count and device ms a
step, and the names of their stats). It also prints the cost
of a profiler span entered with no session active, and the step time of
each window. The last line is one JSON object. Needs one TPU; runs no
reference and judges nothing.
"""
import argparse
import glob
import json
import os
import pathlib
import re
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
#: The reducers of the host split, in the order they are printed.
SPLIT = ("executor_f_host_ms", "executor_b_host_ms", "grad_accum_host_ms",
         "split_merge_host_ms", "interp_host_ms")


def span_cost_us(n: int = 200_000) -> float:
    """Microseconds to enter and leave one instruction span (seven args)
    with no profiler session active."""
    import jax
    t = time.perf_counter()
    for i in range(n):
        with jax.profiler.TraceAnnotation("pipeline.F", op="F", stage=1, mb=i,
                                          chunk=0, sl=0, phase="", step=1):
            pass
    return (time.perf_counter() - t) / n * 1e6


def split(cell, seed, steps, devices, log=print, peaks=None):
    """Set-up, then the three windows; returns the result object."""
    import jax
    from bench import harness, trace as tm

    entry, _, batch_at, cfg = harness.start(cell, seed, devices, log, peaks)
    step = cell.job["check_steps"]

    def window():
        nonlocal step
        t = time.perf_counter()
        for _ in range(steps):
            with harness.span("batch"):
                b = entry.put(batch_at(step))
            entry.step(b, harness.span)
            step += 1
        with harness.span("loss_read"):
            entry.block()
        return (time.perf_counter() - t) / steps

    off_before = window()
    log_dir = tempfile.mkdtemp(prefix="bench-split-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with harness.span("window"):
        on = window()
    jax.profiler.stop_trace()
    off_after = window()
    entry.finish()

    reducers = {name: harness._load_file("metrics", name) for name in SPLIT}
    names = set(harness.HOST_SPANS).union(
        *(r.SPANS for r in reducers.values()))
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    tr = tm.from_profile(pd, names)
    (win,) = tr.spans("window")
    lo, hi = win.start, win.end
    ids = [d.id for d in devices]
    ctx = harness.metric_ctx(cell, cfg, tr, lo, hi, ids, steps, peaks)
    metrics = {m["name"]: harness._load_file("metrics", m["name"]).read(ctx)
               for m in cell.per_layer}
    metrics.update({name: r.read(ctx) for name, r in reducers.items()})
    interp = reducers["interp_host_ms"]
    by_span = {n: tm.length(tm.union(((e.start, e.end) for e in tr.spans(n)),
                                     lo, hi)) / 1e6 / steps
               for n in interp.PIPELINE}
    parts = sum(metrics[n] or 0.0 for n in SPLIT) + sum(
        by_span[f"pipeline.{op}"] for op in interp.MOVES)
    census = {n: len([e for e in tr.spans(n) if lo <= e.start < hi]) / steps
              for n in interp.PIPELINE}

    def idle_by(labels):
        totals = {}
        for name, s in tm.idle_gaps(tr, ids[0], lo, hi, labels, n=None):
            totals[name] = totals.get(name, 0.0) + s
        return totals

    kernels = {}
    for plane in pd.planes:
        if plane.name != f"/device:TPU:{ids[0]}":
            continue
        for line in plane.lines:
            for e in line.events:
                if line.name == "XLA Ops" and tm.opcode(e.name) == "custom-call":
                    head = re.sub(r"\.\d+$", "", e.name.split(" = ", 1)[0])
                    k = kernels.setdefault(head, {
                        "per_step": 0.0, "device_ms": 0.0,
                        "stats": sorted(name for name, _ in e.stats)})
                    k["per_step"] += 1 / steps
                    k["device_ms"] += e.duration_ns / 1e6 / steps
    shutil.rmtree(log_dir, ignore_errors=True)
    return {
        "steps": steps,
        "metrics": metrics,
        "host_ms_by_span": by_span,
        "parts_sum_ms": parts,
        "parts_over_executor_host": parts / metrics["executor_host_ms"]
        if metrics.get("executor_host_ms") else None,
        "spans_per_step": census,
        "idle_s_by_pipeline_span": idle_by(interp.PIPELINE),
        "idle_s_by_host_span": idle_by(harness.HOST_SPANS[1:]),
        "busy_s": tm.length(tm.busy(tr, ids[0], lo, hi)) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "step_s": {"untraced_before": off_before, "traced": on,
                   "untraced_after": off_after},
        "custom_calls": kernels,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    cell = harness.find_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"host_split: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s).",
              file=sys.stderr)
        return 2
    peaks = harness.load_json(ROOT / "bench" / "peaks.json")[
        devices[0].device_kind]
    from repro.launch.cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    cost = span_cost_us()
    print(f"span with no profiler session: {cost:.3f} us", flush=True)
    result = split(cell, args.seed, args.steps or cell.job["trace_steps"],
                   devices[:cell.chips], log=lambda s: print(s, flush=True),
                   peaks=peaks)
    result["span_cost_us"] = cost
    result["off_cost_ms_per_step"] = cost * sum(
        result["spans_per_step"].values()) / 1e3
    for k in ("metrics", "host_ms_by_span", "idle_s_by_pipeline_span",
              "idle_s_by_host_span", "step_s", "custom_calls"):
        print(f"{k}: {result[k]}", flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
