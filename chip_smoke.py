#!/usr/bin/env python3
"""Chip smoke test: the BPipe training path at full model width on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the SPMD pipeline across four chips

One chip. qwen1.5-0.5b at full width (24 layers, d 1024, 16/16 heads at
hd 64, vocab 151,936; bf16 compute, fp32 master params and Adam state),
random weights from a fixed seed, trained through ``PipelineExecutor``
(p=4 stages, m=8 micro-batches of one row) with a jitted ``adam.update``:

  kernel  the flash-attention kernel, forward and backward, against the
          jnp reference (``kernels.ref``) on a small input;
  bpipe   3 steps under ScheduleSpec("bpipe", 4, 8) with remat="attn";
          step-0 loss agrees with the unpipelined ``models.model.loss_fn``;
  1f1b    the same 3 steps under ScheduleSpec("1f1b", 4, 8); its step-0
          loss equals bpipe's exactly;
  flash   1 step with attn_impl="flash", remat="flash"; its loss agrees
          with the reference arm's, and the compiled stage holds the
          Pallas kernel (``tpu_custom_call``).

Four chips (``--four-chips``). ``pipeline/spmd.py`` on a
("data", "model") = (1, 4) mesh: stage params sharded one stage per chip,
loss and grads for ``bpipe_stash`` False and True checked against the
unpipelined ``loss_fn`` on chip 0, and the BPipe variant's extra
collective-permutes counted in the compiled HLO.

Every check raises on failure. The last stdout line,
``{"ok": true, "device": {...}}``, is printed only after all of them
passed. Without a TPU the script exits nonzero and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding  # noqa: E402,E501

from repro.configs import get_config  # noqa: E402
from repro.configs.base import TrainConfig  # noqa: E402
from repro.core.plan import ScheduleSpec  # noqa: E402
from repro.data.pipeline import DataConfig, make_batch  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.optim import adam  # noqa: E402
from repro.pipeline import PipelineExecutor  # noqa: E402
from repro.pipeline import spmd  # noqa: E402
from repro.pipeline.stage import StageSplitter  # noqa: E402

ARCH = "qwen1.5-0.5b"
SEED = 0
STAGES, MICRO = 4, 8          # ScheduleSpec p, m; one row per micro-batch
# One chip holds all four stages, fp32 params, Adam state and the stash:
# seq 1024 ran out of HBM in the bpipe arm's backward on a v5e, so the
# smoke runs at 512 (the published context is 32k).
SEQ = 512
SEQ_CUT_FROM = 1024
STEPS = 3
SPMD_SEQ, SPMD_MICRO = 512, 4  # four chips: the per-tick vocab head bounds it

# bf16 tolerances. Every arm computes in bf16 (8 mantissa bits, relative
# step 2^-8 = 3.9e-3); losses are fp32 means over 8k tokens, so two
# orderings of the same bf16 math stay within about one step of each
# other, and grads (sums of bf16 products) within a few.
LOSS_RTOL = 4e-3              # |loss - reference| / |reference|
GRAD_RTOL = 2e-2              # ||g - g_ref|| / ||g_ref|| over all leaves
KERNEL_RTOL = 2e-2            # flash vs jnp reference, output and grads


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_err(a, b) -> float:
    """Relative L2 distance of two pytrees, over all leaves together."""
    num = sum(jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32)))
              for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    den = sum(jnp.sum(jnp.square(y.astype(jnp.float32)))
              for y in jax.tree.leaves(b))
    return float(jnp.sqrt(num / den))


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)
    log(f"  ok: {what}")


def hbm(device=None) -> str:
    """Bytes in use now and at the process's peak, as the device reports
    them."""
    stats = (device or jax.devices()[0]).memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        return "memory stats not reported"
    return (f"bytes_in_use {stats['bytes_in_use']} "
            f"peak_bytes_in_use {stats['peak_bytes_in_use']} "
            f"({stats['peak_bytes_in_use'] / 2**30:.2f} GiB)")


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------
def kernel_check(shapes=((1, 512, 16, 16, 64), (1, 512, 8, 2, 128))) -> None:
    """Flash attention fwd+bwd on the default device vs the jnp oracle."""
    interpret = jax.default_backend() == "cpu"
    for b, s, nq, nkv, hd in shapes:
        ks = jax.random.split(jax.random.PRNGKey(SEED), 4)
        q = jax.random.normal(ks[0], (b, s, nq, hd), jnp.bfloat16)
        k = jax.random.normal(ks[1], (b, s, nkv, hd), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, s, nkv, hd), jnp.bfloat16)
        g = jax.random.normal(ks[3], (b, s, nq, hd), jnp.bfloat16)

        def fwd_bwd(attn, q, k, v):
            out, vjp = jax.vjp(attn, q, k, v)
            return out, vjp(g)

        flash = functools.partial(ops.flash_attention, interpret=interpret)
        got = jax.jit(functools.partial(fwd_bwd, flash))(q, k, v)
        want = jax.jit(functools.partial(fwd_bwd, ref.flash_attention_ref))(
            q, k, v)
        err_o, err_g = rel_err(got[0], want[0]), rel_err(got[1], want[1])
        log(f"kernel b={b} s={s} heads={nq}/{nkv} hd={hd}: "
            f"out rel err {err_o:.3e}, grad rel err {err_g:.3e}")
        check(err_o < KERNEL_RTOL and err_g < KERNEL_RTOL,
              f"flash kernel agrees with the reference within {KERNEL_RTOL}")


def train_arm(name, cfg, spec, remat, batches, steps):
    """Train ``steps`` steps from the seed's params; returns the per-step
    losses and the executor."""
    params = jax.jit(M.init_params, static_argnums=1)(
        jax.random.PRNGKey(SEED), cfg)
    opt = adam.init(params)
    tcfg = TrainConfig(global_batch=batches[0]["tokens"].shape[0],
                       micro_batch=1, seq_len=batches[0]["tokens"].shape[1],
                       steps=steps, warmup_steps=steps, learning_rate=1e-4,
                       remat=remat, seed=SEED)
    update = jax.jit(functools.partial(adam.update, tcfg=tcfg),
                     donate_argnums=(0, 2))
    ex = PipelineExecutor(cfg, spec=spec, micro_batch=1, remat=remat)
    losses = []
    for i in range(steps):
        t0 = time.perf_counter()
        r = ex.step(params, batches[i])
        loss = float(r.loss)
        params, opt, metrics = update(params, r.grads, opt)
        gnorm = float(metrics["grad_norm"])
        losses.append(loss)
        st = r.stats
        log(f"{name} step {i}: loss {loss!r} grad_norm {gnorm:.4f} "
            f"({time.perf_counter() - t0:.1f} s host wall"
            f"{', compiles included' if i == 0 else ''})")
        log(f"  stash: peak units/stage {dict(st.peak_local)} "
            f"evictions {st.evictions} loads {st.loads} "
            f"model peak MiB/stage "
            f"{ {k: round(v / 2**20, 1) for k, v in st.peak_bytes.items()} }")
        log(f"  hbm: {hbm()}")
        check(np.isfinite(loss) and np.isfinite(gnorm),
              f"{name} step {i} loss and grad norm are finite")
    return losses, ex


def reference_loss(cfg, batch) -> float:
    """Unpipelined ``loss_fn`` on the seed's params, one micro-batch row
    at a time (the full batch's fp32 logits would not fit), averaged the
    way the executor averages micro-batches."""
    params = jax.jit(M.init_params, static_argnums=1)(
        jax.random.PRNGKey(SEED), cfg)
    f = jax.jit(lambda p, b: M.loss_fn(p, b, cfg)[0])
    rows = batch["tokens"].shape[0]
    per = [float(f(params, {k: v[j:j + 1] for k, v in batch.items()}))
           for j in range(rows)]
    return float(np.mean(per))


def stage_hlo(ex, cfg, seq) -> str:
    """Compiled HLO of the executor's middle stage forward."""
    shapes = jax.eval_shape(
        lambda k: StageSplitter(cfg, ex.n_virtual).split(
            M.init_params(k, cfg)), jax.random.PRNGKey(SEED))
    vs = 1
    carry = (jax.ShapeDtypeStruct((1, seq, cfg.d_model), jnp.dtype(cfg.dtype)),
             jax.ShapeDtypeStruct((), jnp.float32))
    micro = {k: jax.ShapeDtypeStruct((1, seq), jnp.int32)
             for k in ("tokens", "labels")}
    return ex.stage_fns[vs].lower(shapes[vs], carry, micro).compile().as_text()


def one_chip(cfg, *, seq=SEQ, batch=MICRO, steps=STEPS,
             kernel_marker="tpu_custom_call", kernel_shapes=None) -> None:
    log(f"config {cfg.name}: layers {cfg.num_layers} d_model {cfg.d_model} "
        f"heads {cfg.num_heads}/{cfg.num_kv_heads} hd {cfg.head_dim} "
        f"vocab {cfg.vocab_size} dtype {cfg.dtype}; weights random (seed {SEED})")
    log(f"shape: global batch {batch} x seq {seq}, micro-batch 1, "
        f"p={STAGES} m={batch}; cuts: seq 32768 (published context) -> "
        f"{SEQ_CUT_FROM} (start) -> {seq} ({SEQ_CUT_FROM} exceeds one "
        f"chip's HBM), depth not cut")
    kernel_check(**({"shapes": kernel_shapes} if kernel_shapes else {}))

    dc = DataConfig(batch=batch, seq_len=seq, seed=SEED)
    batches = [{k: jnp.asarray(v) for k, v in make_batch(cfg, dc, i).items()}
               for i in range(steps)]

    want = reference_loss(cfg, batches[0])
    log(f"loss_fn step-0 reference loss {want!r}; hbm: {hbm()}")
    bp, _ = train_arm("bpipe", cfg, ScheduleSpec("bpipe", STAGES, batch),
                         "attn", batches, steps)
    check(abs(bp[0] - want) <= LOSS_RTOL * abs(want),
          f"bpipe step-0 loss {bp[0]!r} agrees with loss_fn {want!r} "
          f"(rtol {LOSS_RTOL})")
    fb, _ = train_arm("1f1b", cfg, ScheduleSpec("1f1b", STAGES, batch),
                         "attn", batches, steps)
    check(fb[0] == bp[0], f"1f1b step-0 loss {fb[0]!r} equals bpipe's")

    cfg_fa = dataclasses.replace(cfg, attn_impl="flash")
    fl, ex = train_arm("flash", cfg_fa,
                          ScheduleSpec("bpipe", STAGES, batch), "flash",
                          batches, 1)
    check(abs(fl[0] - bp[0]) <= LOSS_RTOL * abs(bp[0]),
          f"flash step-0 loss {fl[0]!r} agrees with the reference arm "
          f"{bp[0]!r} (rtol {LOSS_RTOL})")
    if kernel_marker:
        n = stage_hlo(ex, cfg_fa, seq).count(kernel_marker)
        check(n > 0, f"flash stage HLO holds {n} {kernel_marker} op(s)")


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------
def _model_params(pp):
    """Stage-stacked SPMD params -> the ``models.model`` layout (stage i,
    local layer j is layer i * per + j), so ``loss_fn`` is the
    reference."""
    def restack(*per_layer):          # each leaf (p, ...) -> (p*per, ...)
        x = jnp.stack(per_layer, axis=1)
        return x.reshape((-1,) + x.shape[2:])
    return {"embed": pp["embed"], "final_norm": pp["final_norm"],
            "blocks": {"pos0": jax.tree.map(restack, *pp["stages"])}}


def four_chips(cfg, devices, *, seq=SPMD_SEQ, num_micro=SPMD_MICRO) -> None:
    p = len(devices)
    assert cfg.block_pattern == (cfg.block_pattern[0],), "uniform stack"
    mesh = jax.make_mesh((1, p), ("data", "model"), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    log(f"config {cfg.name}: layers {cfg.num_layers} d_model {cfg.d_model} "
        f"vocab {cfg.vocab_size} dtype {cfg.dtype}; weights random "
        f"(seed {SEED})")
    log(f"shape: mesh (data, model) = (1, {p}) over "
        f"{[d.id for d in devices]}; global batch {num_micro} x seq {seq}, "
        f"{num_micro} micro-batches of 1 row; cuts: seq {seq} (the per-tick "
        f"full-vocab head runs on every stage), depth not cut")
    init = functools.partial(spmd.init_pipeline_params, cfg=cfg, p=p)
    shapes = jax.eval_shape(init, jax.random.PRNGKey(SEED))
    shard = {"stages": jax.tree.map(
        lambda _: NamedSharding(mesh, PartitionSpec("model")),
        shapes["stages"]),
        "embed": jax.tree.map(lambda _: NamedSharding(mesh, PartitionSpec()),
                              shapes["embed"]),
        "final_norm": jax.tree.map(
            lambda _: NamedSharding(mesh, PartitionSpec()),
            shapes["final_norm"])}
    params = jax.jit(init, out_shardings=shard)(jax.random.PRNGKey(SEED))
    spans = {len(a.sharding.device_set)
             for a in jax.tree.leaves(params["stages"])}
    check(spans == {p}, f"stage params span {p} devices (saw {spans})")

    dc = DataConfig(batch=num_micro, seq_len=seq, seed=SEED)
    batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, dc, 0).items()}

    chip0 = SingleDeviceSharding(devices[0])
    params0 = jax.device_put(params, chip0)
    batch0 = jax.device_put(batch, chip0)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda pp, b: M.loss_fn(_model_params(pp), b, cfg)[0]))(
            params0, batch0)
    ref_loss = float(ref_loss)
    log(f"reference loss_fn on chip {devices[0].id}: {ref_loss!r}")

    permutes = {}
    for bpipe in (False, True):
        lossf = spmd.make_spmd_train_loss(cfg, mesh, p, num_micro,
                                          bpipe_stash=bpipe)
        t0 = time.perf_counter()
        with jax.set_mesh(mesh):
            step = jax.jit(jax.value_and_grad(lossf)).lower(
                params, batch).compile()
        permutes[bpipe] = step.as_text().count("collective-permute")
        loss, grads = step(params, batch)
        loss = float(loss)
        g_err = rel_err(jax.device_put(grads, chip0), ref_grads)
        del grads
        log(f"spmd bpipe_stash={bpipe}: loss {loss!r} grad rel err "
            f"{g_err:.3e}, {permutes[bpipe]} collective-permutes "
            f"({time.perf_counter() - t0:.1f} s host wall, compile "
            f"included)")
        check(abs(loss - ref_loss) <= LOSS_RTOL * abs(ref_loss),
              f"bpipe_stash={bpipe} loss agrees with loss_fn "
              f"(rtol {LOSS_RTOL})")
        check(g_err <= GRAD_RTOL,
              f"bpipe_stash={bpipe} grads agree with loss_fn's "
              f"(rtol {GRAD_RTOL})")
    check(permutes[True] > permutes[False],
          f"BPipe stash adds collective-permutes ({permutes[False]} -> "
          f"{permutes[True]})")
    log(f"chip {devices[0].id} hbm: {hbm(devices[0])}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the SPMD pipeline across four chips")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is {dev.platform}); "
              "nothing was run", file=sys.stderr)
        return 1
    log(f"cache: {enable_compile_cache()}")
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    cfg = get_config(ARCH)
    if args.four_chips:
        if len(devices) < 4:
            print(f"chip_smoke: --four-chips needs 4 devices, found "
                  f"{len(devices)}", file=sys.stderr)
            return 1
        four_chips(cfg, devices[:4])
    else:
        one_chip(cfg)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
