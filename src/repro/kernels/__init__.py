"""Pallas TPU kernels for the paper's perf-critical compute hot-spots:

  flash_attention.py — flash-attention-2 adapted to VMEM/MXU tiling
    (fwd with online softmax + LSE output, two-pass bwd, block-sparse
    skipping, GQA/window/softcap support)
  fused_softmax.py   — the §3.2 fused scale+mask+softmax chain (fwd+bwd)

ops.py = jit-ready custom_vjp wrappers; ref.py = pure-jnp oracles that
every kernel test asserts against (interpret mode on the CPU).
"""
