import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Pipeline-parallel dry-run of the paper's own configuration, at
production-mesh scale: GPT-3 96B (and LLaMA 65B) with the "model" axis
carrying p=16 pipeline stages (the paper's Fig. 2 16-way setup),
data-parallel over the remaining axes, with and without the BPipe
activation-offload pattern (pipeline/spmd.py).

    PYTHONPATH=src python -m repro.launch.pipeline_dryrun [--arch gpt3-96b]

Writes experiments/dryrun/pipeline__<arch>__<mesh>__<variant>.json with
collective-permute counts/bytes (the eviction hops) + memory analysis.
"""
import argparse
import json
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.pipeline.spmd import init_pipeline_params, make_spmd_train_loss

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def run(arch: str, mesh_kind: str, bpipe: bool, *, p=16, B=128, s=2048,
        num_micro=None, out_dir=None):
    cfg = get_config(arch)
    assert cfg.num_layers % p == 0
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    # microbatches stream per data shard: num_micro must divide the
    # local batch (B / data-axes product)
    data = 1
    for a in mesh.axis_names:
        if a != "model":
            data *= mesh.shape[a]
    local_b = max(B // data, 1)
    num_micro = num_micro or local_b
    lossf = make_spmd_train_loss(cfg, mesh, p, num_micro=num_micro,
                                 bpipe_stash=bpipe)
    pshape = jax.eval_shape(
        lambda k: init_pipeline_params(k, cfg, p), jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((B, s), jnp.int32),
             "labels": jax.ShapeDtypeStruct((B, s), jnp.int32)}

    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = jax.jit(jax.grad(lossf)).lower(pshape, batch)
        compiled = lowered.compile()
    t_compile = time.time() - t0
    ma = compiled.memory_analysis()
    txt = compiled.as_text()
    coll = rl.collective_bytes(txt)
    rec = {
        "arch": arch, "mesh": mesh_kind, "p": p, "num_micro": num_micro,
        "bpipe_stash": bpipe, "t_compile_s": round(t_compile, 2),
        "memory": {"argument_bytes": ma.argument_size_in_bytes,
                   "temp_bytes": ma.temp_size_in_bytes},
        "collective_bytes": coll,
        "collective_permute_ops": txt.count(" collective-permute"),
    }
    out_dir = out_dir or os.path.abspath(OUT_DIR)
    os.makedirs(out_dir, exist_ok=True)
    name = f"pipeline__{arch}__{mesh_kind}__{'bpipe' if bpipe else '1f1b'}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=1)
    print(f"OK pipeline {arch} {mesh_kind} bpipe={bpipe} "
          f"compile={t_compile:.1f}s temp={ma.temp_size_in_bytes/2**30:.1f}GiB "
          f"cp_ops={rec['collective_permute_ops']} "
          f"cp_bytes={coll['collective-permute']/2**30:.2f}GiB", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", nargs="*", default=["gpt3-96b", "llama-65b"])
    ap.add_argument("--mesh", nargs="*", default=["single", "multi"])
    args = ap.parse_args()
    for arch in args.arch:
        for mesh_kind in args.mesh:
            for bpipe in (False, True):
                try:
                    run(arch, mesh_kind, bpipe)
                except Exception as e:  # noqa: BLE001
                    print(f"FAIL pipeline {arch} {mesh_kind} bpipe={bpipe}: "
                          f"{e!r}", flush=True)


if __name__ == "__main__":
    main()
