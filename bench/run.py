#!/usr/bin/env python3
"""Chip benchmark of the BPipe training path.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine and prints,
as its last line, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: each number compared with its limit. The same checks end its
standard error. Without a TPU, or with fewer chips than the cell asks
for, it prints no result and exits 2.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: the program is not in this checkout ({src}/repro)",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(src)]
    from bench import harness

    cell = harness.find_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s). "
              "Nothing was run.", file=sys.stderr)
        return 2
    peaks = harness.load_json(ROOT / "bench" / "peaks.json")
    kind = devices[0].device_kind
    if kind not in peaks:
        print(f"bench: no peaks for device kind {kind!r} in "
              "bench/peaks.json", file=sys.stderr)
        return 2

    from repro.launch.cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         t0=T0, devices=devices[:cell.chips],
                         log=lambda s: print(s, flush=True),
                         peaks=peaks[kind])
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
