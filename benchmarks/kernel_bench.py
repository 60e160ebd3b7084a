"""Kernel microbenchmarks (interpret mode on the CPU backend — relative
numbers only, never a device metric)
+ the §3.2 fusion-count analysis: on TPU, XLA fuses the paper's
upcast-scale-softmax-downcast chain into ~1 fusion, so the exp-(7)
pathology that made BPipe look good on GPT-3 cannot occur (DESIGN.md §3).

Columns: name, us_per_call, derived (fusion/kernel counts, speedup).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


def _time(f, *args, iters=5):
    f(*args)  # compile/warm
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(f(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def fusion_count(f, *args) -> int:
    txt = jax.jit(f).lower(*args).compile().as_text()
    return txt.count(" fusion(") + txt.count(" fusion.")


def main(print_csv=True, smoke=False):
    key = jax.random.PRNGKey(0)
    interpret = jax.default_backend() == "cpu"
    mode = f"interpret_mode={int(interpret)}"
    rows = []
    sm_shape = (1, 2, 64, 64) if smoke else (4, 8, 256, 256)
    seqs = (64,) if smoke else (128, 256)

    # --- fused softmax: XLA-fused chain vs Pallas kernel -------------------
    x = jax.random.normal(key, sm_shape, jnp.bfloat16)
    t_unfused = _time(jax.jit(
        lambda x: ops.unfused_softmax_chain(x, 0.125, True)), x)
    t_pallas = _time(jax.jit(
        lambda x: ops.fused_softmax(x, 0.125, True, 128, interpret)), x)
    nf = fusion_count(lambda x: ops.unfused_softmax_chain(x, 0.125, True), x)
    rows.append(("softmax_xla_chain", t_unfused, f"xla_fusions={nf}"))
    rows.append(("softmax_pallas_interpret", t_pallas, mode))

    # --- flash attention vs reference --------------------------------------
    for s in seqs:
        q = jax.random.normal(key, (1, s, 8, 64), jnp.bfloat16)
        k = jax.random.normal(key, (1, s, 2, 64), jnp.bfloat16)
        v = jax.random.normal(key, (1, s, 2, 64), jnp.bfloat16)
        blk = min(s, 128)
        t_ref = _time(jax.jit(lambda q, k, v: ref.flash_attention_ref(
            q, k, v, causal=True)), q, k, v)
        t_fa = _time(jax.jit(lambda q, k, v: ops.flash_attention(
            q, k, v, True, 0, 0.0, None, blk, blk, interpret)), q, k, v)
        rows.append((f"flash_attn_ref_s{s}", t_ref, "jnp"))
        rows.append((f"flash_attn_pallas_s{s}", t_fa, mode))

    if print_csv:
        for name, us, derived in rows:
            print(f"kernel_bench,{name},{us:.1f},{derived}")
    return rows


if __name__ == "__main__":
    main()
