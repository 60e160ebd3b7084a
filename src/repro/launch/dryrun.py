import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes, record memory / cost / collective artifacts.

    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b \
        --shape train_4k --mesh single multi

Per combo this writes experiments/dryrun/<arch>__<shape>__<mesh>.json:
  * compile wall time, per-device memory analysis (args/outputs/temps),
  * raw cost_analysis (scan-body-once caveat — see launch/roofline.py),
  * roofline-extrapolated per-device FLOPs / HBM bytes / collective bytes
    from unrolled 1-block and 2-block variants (single-pod only),
  * the three roofline terms + dominant bottleneck.
"""
import argparse
import dataclasses
import json
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED, INPUT_SHAPES, get_config, shape_applicable
from repro.configs.base import TrainConfig
from repro.core import flops as flops_mod
from repro.launch import roofline as rl
from repro.launch import specs as sp
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.optim import adam
from repro.sharding import rules

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _ns(mesh, spec):
    return NamedSharding(mesh, spec)


def _tree_shardings(mesh, specs):
    return jax.tree.map(lambda s: _ns(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# --- §Perf variants: each is (cfg overrides, tcfg overrides, cache strategy)
VARIANTS = {
    "baseline": ({}, {}, "heads"),
    "fused_xent": ({"fused_xent": True}, {}, "heads"),
    "remat_none": ({}, {"remat": "none"}, "heads"),
    "remat_full": ({}, {"remat": "full"}, "heads"),
    "cache_seq": ({}, {}, "seq"),
    "cache_auto": ({}, {}, "auto"),
    "moe_a2a": ({"moe_constrained": True}, {}, "heads"),
    "fused_xent+remat_full": ({"fused_xent": True}, {"remat": "full"}, "heads"),
    "fused_xent+moe_a2a": ({"fused_xent": True, "moe_constrained": True},
                           {}, "heads"),
    "bf16_scores": ({"attn_fp32": False}, {}, "heads"),
    "moe_fsdp": ({}, {}, "heads", "data"),
    "moe_fsdp+a2a": ({"moe_constrained": True}, {}, "heads", "data"),
    "bf16_scores+remat_none": ({"attn_fp32": False}, {"remat": "none"},
                               "heads"),
    "window1k": ({"block_pattern": ("local_attn",), "window_size": 1024},
                 {}, "heads"),  # quantifies the s^2-score traffic share
    # the paper's own axis: micro batch size (grad accumulation)
    "accum_b8": ({}, {"micro_batch": 8}, "heads"),
    # pad q heads to the model-axis multiple (+20% attn flops for qwen3)
    # to test the head-divisibility hypothesis for the prefill collectives
    "pad_heads48": ({"num_heads": 48}, {}, "heads"),
    "pad_heads48_mha": ({"num_heads": 48, "num_kv_heads": 48}, {}, "heads"),
    "accum_b8+remat_none": ({}, {"micro_batch": 8, "remat": "none"}, "heads"),
    "moe_fsdp+accum_b8": ({}, {"micro_batch": 8}, "heads", "data"),
    "moe_a2a+accum_b8": ({"moe_constrained": True}, {"micro_batch": 8},
                         "heads"),
}


def build_step(cfg, shape, mesh, tcfg: TrainConfig, cache_strategy="heads",
               moe_axis="model"):
    """Returns (fn, arg_specs, in_shardings) for this shape kind."""
    pspec = sp.param_specs(cfg)
    p_sh = _tree_shardings(mesh, rules.param_specs(pspec, mesh, moe_axis))
    if shape.kind == "train":
        batch = sp.train_batch_specs(cfg, shape)
        o_spec = sp.opt_specs(pspec)
        o_sh = jax.tree.map(
            lambda s: s, adam.AdamState(
                step=_ns(mesh, P()),
                m=_tree_shardings(mesh, rules.param_specs(pspec, mesh, moe_axis)),
                v=_tree_shardings(mesh, rules.param_specs(pspec, mesh, moe_axis))))
        b_sh = _tree_shardings(mesh, rules.batch_specs(batch, mesh))

        num_micro = max(1, shape.global_batch // tcfg.micro_batch) \
            if tcfg.micro_batch else 1

        def step(params, opt_state, b):
            if num_micro == 1:
                (loss, metrics), grads = jax.value_and_grad(
                    lambda p: M.loss_fn(p, b, cfg, remat=tcfg.remat),
                    has_aux=True)(params)
            else:
                # paper's b-axis: microbatched gradient accumulation.
                # Live activations scale with micro_batch, not B.
                mb = {k: v.reshape((num_micro, -1) + v.shape[1:])
                      for k, v in b.items()}

                def acc(carry, bi):
                    g_sum, l_sum = carry
                    (l, _), g = jax.value_and_grad(
                        lambda p: M.loss_fn(p, bi, cfg, remat=tcfg.remat),
                        has_aux=True)(params)
                    return (jax.tree.map(jnp.add, g_sum, g), l_sum + l), None

                zeros = jax.tree.map(
                    lambda a: jnp.zeros(a.shape, jnp.float32), params)
                (grads, loss), _ = jax.lax.scan(acc, (zeros, 0.0), mb)
                grads = jax.tree.map(lambda g: g / num_micro, grads)
                loss = loss / num_micro
                metrics = {"loss": loss, "aux": 0.0}
            params, opt_state, om = adam.update(params, grads, opt_state, tcfg)
            return params, opt_state, dict(metrics, **om)

        return (step, (pspec, o_spec, batch), (p_sh, o_sh, b_sh),
                (p_sh, o_sh, None))

    if shape.kind == "prefill":
        batch = sp.prefill_batch_specs(cfg, shape)
        state = sp.decode_state_specs(cfg, shape)
        b_sh = _tree_shardings(mesh, rules.batch_specs(batch, mesh))
        s_sh = _tree_shardings(mesh, rules.cache_specs(state, mesh,
                                                       cache_strategy, cfg))

        def step(params, b, state):
            logits, state, _ = M.prefill(params, b, cfg, state)
            return logits, state

        return step, (pspec, batch, state), (p_sh, b_sh, s_sh), None

    # decode
    state = sp.decode_state_specs(cfg, shape)
    dec_in = sp.decode_input_specs(cfg, shape)
    s_sh = _tree_shardings(mesh, rules.cache_specs(state, mesh,
                                                   cache_strategy, cfg))
    ba = rules.batch_axes(mesh)
    tok_sh = _ns(mesh, rules.legalize(P(ba), dec_in["token"].shape, mesh))
    pos_sh = _ns(mesh, P())
    args = [pspec, state, dec_in["token"], dec_in["pos"]]
    shards = [p_sh, s_sh, tok_sh, pos_sh]
    if cfg.is_encdec:
        args.append(dec_in["enc_states"])
        shards.append(_ns(mesh, rules.legalize(
            P(ba, None, None), dec_in["enc_states"].shape, mesh)))

        def step(params, state, token, pos, enc_states):
            logits, state = M.decode_step(params, token, pos, state, cfg,
                                          enc_states=enc_states)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            return nxt, state
    else:
        def step(params, state, token, pos):
            logits, state = M.decode_step(params, token, pos, state, cfg)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            return nxt, state

    return step, tuple(args), tuple(shards), None


def lower_combo(cfg, shape, mesh, tcfg, cache_strategy="heads",
                moe_axis="model") -> Dict:
    rules.RELOCATIONS.clear()
    fn, args, in_sh, out_sh = build_step(cfg, shape, mesh, tcfg,
                                         cache_strategy, moe_axis)
    relocs = sorted({(t, d, -1 if d2 is None else d2)
                     for t, _, d, d2, _ in rules.RELOCATIONS})
    if relocs:
        print(f"WARN sharding relocations (collective hazard, see "
              f"EXPERIMENTS HC-5): {relocs}", flush=True)
    t0 = time.time()
    jitted = (jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
              if out_sh is not None else jax.jit(fn, in_shardings=in_sh))
    with jax.set_mesh(mesh):  # enables with_sharding_constraint(P(...))
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
    t_compile = time.time() - t0
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    coll = rl.collective_bytes(txt)
    return {
        "t_lower_s": round(t_lower, 2), "t_compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
        },
        "cost_raw": {"flops": float(ca.get("flops", 0.0)),
                     "bytes_accessed": float(ca.get("bytes accessed", 0.0))},
        "collective_bytes_raw": coll,
        "hlo_collective_ops": {
            k: txt.count(f" {k}") for k in rl.COLLECTIVES},
    }


def variant_cfg(cfg, k: int):
    """Unrolled k-block variant (full dims) for roofline extraction."""
    kw = dict(num_layers=len(cfg.block_pattern) * k, scan_blocks=False)
    if cfg.encoder_layers:
        kw["encoder_layers"] = k
    return dataclasses.replace(cfg, **kw)


def effective_blocks(cfg) -> float:
    pat = len(cfg.block_pattern)
    return cfg.num_layers / pat


def roofline_combo(cfg, shape, mesh, tcfg, cache_strategy="heads",
                   moe_axis="model") -> Dict:
    """Extrapolated per-device roofline terms via 1- vs 2-block unrolls."""
    res = {}
    for k in (1, 2):
        r = lower_combo(variant_cfg(cfg, k), shape, mesh, tcfg,
                        cache_strategy, moe_axis)
        res[k] = {"flops": r["cost_raw"]["flops"],
                  "bytes": r["cost_raw"]["bytes_accessed"],
                  "coll": sum(r["collective_bytes_raw"].values()),
                  **{f"coll_{kk}": v
                     for kk, v in r["collective_bytes_raw"].items()}}
    n = effective_blocks(cfg)
    ext = rl.extrapolate(res[1], res[2], n)
    terms = rl.RooflineTerms(
        flops=ext["flops"], bytes_hbm=ext["bytes"],
        bytes_collective=ext["coll"], chips=mesh.devices.size)
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "decode":
        # serve_step does not rerun the encoder (enc_states are an input)
        model_flops = flops_mod.model_flops_fwd(cfg, b, 1,
                                                include_encoder=False)
    elif shape.kind == "prefill":
        model_flops = flops_mod.model_flops_fwd(cfg, b, s)
    else:
        model_flops = flops_mod.model_flops_train(cfg, b, s)
    mf_dev = model_flops / mesh.devices.size
    return {
        "per_block_points": res,
        "extrapolated": ext,
        "terms": terms.to_dict(),
        "model_flops_per_device": mf_dev,
        "useful_fraction": (mf_dev / ext["flops"]) if ext["flops"] else None,
        "roofline_mfu": terms.mfu(mf_dev),
    }


def run_one(arch: str, shape_name: str, mesh_kind: str,
            *, with_roofline: bool, out_dir: str, force=False,
            variant: str = "baseline") -> Optional[str]:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if not shape_applicable(cfg, shape):
        return None
    spec = VARIANTS[variant]
    cfg_over, tcfg_over, cache_strategy = spec[0], spec[1], spec[2]
    moe_axis = spec[3] if len(spec) > 3 else "model"
    cfg = dataclasses.replace(cfg, **cfg_over)
    os.makedirs(out_dir, exist_ok=True)
    suffix = "" if variant == "baseline" else f"__{variant}"
    path = os.path.join(out_dir,
                        f"{arch}__{shape_name}__{mesh_kind}{suffix}.json")
    if os.path.exists(path) and not force:
        return path
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    # micro_batch=0 disables grad accumulation (single-shot baseline);
    # the accum_* variants set the paper's b explicitly.
    tcfg = TrainConfig(global_batch=shape.global_batch,
                       seq_len=shape.seq_len, remat="attn", micro_batch=0)
    tcfg = dataclasses.replace(tcfg, **tcfg_over)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "variant": variant, "chips": int(mesh.devices.size),
           "params": cfg.param_count()}
    rec["full"] = lower_combo(cfg, shape, mesh, tcfg, cache_strategy,
                              moe_axis)
    if with_roofline and mesh_kind == "single":
        rec["roofline"] = roofline_combo(cfg, shape, mesh, tcfg,
                                         cache_strategy, moe_axis)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", nargs="*", default=None)
    ap.add_argument("--shape", nargs="*", default=None)
    ap.add_argument("--mesh", nargs="*", default=["single", "multi"],
                    choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-roofline", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="baseline", choices=sorted(VARIANTS))
    ap.add_argument("--out", default=os.path.abspath(OUT_DIR))
    args = ap.parse_args()

    archs = args.arch or (list(ASSIGNED) if args.all else ["qwen1.5-0.5b"])
    shapes = args.shape or (list(INPUT_SHAPES) if args.all else ["train_4k"])

    for arch in archs:
        for shape_name in shapes:
            for mesh_kind in args.mesh:
                t0 = time.time()
                try:
                    path = run_one(arch, shape_name, mesh_kind,
                                   with_roofline=not args.no_roofline,
                                   out_dir=args.out, force=args.force,
                                   variant=args.variant)
                except Exception as e:  # noqa: BLE001 — report & continue
                    print(f"FAIL {arch} {shape_name} {mesh_kind}: {e!r}",
                          flush=True)
                    continue
                if path is None:
                    print(f"SKIP {arch} {shape_name} {mesh_kind} "
                          f"(not applicable)", flush=True)
                else:
                    with open(path) as f:
                        rec = json.load(f)
                    dom = rec.get("roofline", {}).get("terms", {}).get(
                        "dominant", "-")
                    print(f"OK   {arch} {shape_name} {mesh_kind} "
                          f"compile={rec['full']['t_compile_s']}s "
                          f"temp={rec['full']['memory']['temp_bytes']/2**30:.2f}GiB "
                          f"dominant={dom} ({time.time()-t0:.0f}s)",
                          flush=True)


if __name__ == "__main__":
    main()
