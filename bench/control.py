#!/usr/bin/env python3
"""Readings that set a cell's limits (not part of a benchmark run).

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--variants 0]

For each seed, in one process: the program's set-up steps (as a run makes
them) and the float32 reference, compared by ``bench/check.py`` and
printed as one JSON line. With ``--variants 1`` (the default) the
reference is also put in the program's place with a planted fault:
``control`` computes every matrix product on operands rounded to scaled
float8 (e4m3), the precision below the configuration's bfloat16;
``half_batch`` trains on half the rows, the mean taken over them;
``token_altered`` changes one input token of the batch, and
``row_altered`` every token of one row (one micro-batch);
``decay_all_but_final_norm`` decays every leaf but the final norm's
scale, as the program's Adam does to its stacked layer leaves. A state
left unchanged reads update_gap 1 by construction.
"""
import argparse
import copy
import gc
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def half_batch(batches):
    out = []
    for b in batches:
        h = b["tokens"].shape[0] // 2
        out.append({k: v[:h].repeat(2, axis=0) for k, v in b.items()})
    return out


def token_altered(batches, vocab):
    out = copy.deepcopy(batches)
    for b in out:
        s = b["tokens"].shape[1] // 2
        b["tokens"][0, s] = (b["tokens"][0, s] + 1) % vocab
    return out


def row_altered(batches, vocab):
    out = copy.deepcopy(batches)
    for b in out:
        b["tokens"][0] = (b["tokens"][0] + 1) % vocab
    return out


def readings(cell, seed, devices, variants=True, log=print):
    """``{variant: check.gaps(...)}`` for one seed: ``program`` and, with
    ``variants``, each reference variant of the module docstring."""
    import jax
    from bench import check, harness, reference
    job, model = cell.job, cell.config["model"]
    key = reference.seed_key(seed)
    entry, prog, batch_at, _ = harness.start(cell, seed, devices, log)
    entry.finish()
    del entry
    gc.collect()
    batches = [batch_at(s) for s in range(job["check_steps"])]
    opt, vocab = job["optimizer"], model["vocab_size"]

    def ref(b=batches, **kw):
        return reference.train_readings(cell.arch, model, opt, b, key,
                                        devices=devices, **kw)
    want = ref()
    out = {"program": check.gaps(prog, want)}
    if variants:
        out["control"] = check.gaps(ref(quant="fp8"), want)
        out["half_batch"] = check.gaps(ref(half_batch(batches)), want)
        out["token_altered"] = check.gaps(ref(token_altered(batches, vocab)),
                                          want)
        out["row_altered"] = check.gaps(ref(row_altered(batches, vocab)),
                                        want)
        out["decay_all_but_final_norm"] = check.gaps(
            ref(decays=lambda name: name != "scale"), want)
    jax.clear_caches()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", type=int, default=1)
    args = ap.parse_args(argv)
    import jax
    from bench import harness
    from repro.launch.cache import enable_compile_cache
    cell = harness.find_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("control: needs the cell's TPU chips", file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        out = readings(cell, seed, devices[:cell.chips], bool(args.variants),
                       log=lambda s: print(s, file=sys.stderr, flush=True))
        print(json.dumps({"seed": seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
