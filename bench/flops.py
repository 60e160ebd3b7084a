"""Operation and byte counts the benchmark divides by the chip's peaks.

``model_flops_train`` is copied from ``repro.core.flops`` (matmul FLOPs of
forward + backward = 3 x forward, causal attention counted as half the
square, the logits counted once, recomputation left out), so that a later
change to the program cannot change the yardstick. ``tests/test_flops.py``
holds the copy equal to the original for every registered configuration.

``flash_attention_work`` counts the causal attention work of one
forward + backward call from its shapes alone: the same numbers whatever
implements the kernel.
"""
from __future__ import annotations

ATTN, LOCAL, RGLRU, MLSTM, SLSTM = ("attn", "local_attn", "rglru", "mlstm",
                                    "slstm")
ENCODER_FRAMES = 1500  # the program's stub encoder length (whisper)


def layer_flops_fwd(cfg, kind: str, b: int, s: int) -> float:
    """Forward matmul FLOPs of one layer over b rows of s tokens."""
    d, hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    f = 0.0
    if kind in (ATTN, LOCAL):
        f += 2 * b * s * d * hd * (nq + 2 * nkv)          # qkv projections
        f += 2 * b * s * nq * hd * d                      # output projection
        ctx = min(s, cfg.window_size) if (kind == LOCAL and cfg.window_size) else s
        f += 2 * 2 * b * nq * s * ctx * hd * 0.5          # qk^T and pv, causal half
    elif kind == RGLRU:
        w = cfg.rnn_width
        f += 2 * b * s * (2 * d * w + w * d)
        f += 2 * b * s * (2 * w * w)
    elif kind in (MLSTM, SLSTM):
        f += 2 * b * s * d * nq * hd * 4
        f += 2 * b * s * nq * hd * d
        if kind == MLSTM:
            L = cfg.chunk_size
            f += 2 * b * s * nq * (L * hd + 2 * hd * hd)
        else:
            f += 2 * b * s * nq * hd * hd * 4
    if cfg.moe is not None:
        e = cfg.moe
        f += 2 * b * s * d * e.num_experts
        f += 2 * b * s * e.top_k * e.capacity_factor * 3 * d * e.d_ff
        if e.shared_expert:
            f += 2 * b * s * 3 * d * e.d_ff
    elif cfg.d_ff:
        n_mat = 3 if cfg.mlp_kind == "swiglu" else 2
        f += 2 * b * s * n_mat * d * cfg.d_ff
    return f


def model_flops_fwd(cfg, b: int, s: int) -> float:
    f = sum(layer_flops_fwd(cfg, k, b, s) for k in cfg.layer_kinds())
    if cfg.encoder_layers:
        f += cfg.encoder_layers * layer_flops_fwd(cfg, ATTN, b, ENCODER_FRAMES)
        f += cfg.num_layers * 2 * b * ENCODER_FRAMES * 2 * cfg.d_model \
            * cfg.num_kv_heads * cfg.head_dim
        f += cfg.num_layers * 2 * b * s * (
            cfg.d_model * cfg.num_heads * cfg.head_dim * 2
            + 2 * cfg.num_heads * ENCODER_FRAMES * cfg.head_dim)
    f += 2 * b * s * cfg.d_model * cfg.vocab_size         # logits
    return f


def model_flops_train(cfg, b: int, s: int) -> float:
    """Model FLOPs of one training step over b rows of s tokens."""
    return 3.0 * model_flops_fwd(cfg, b, s)


def flash_attention_work(b: int, s: int, nq: int, nkv: int, hd: int,
                         itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one causal attention forward + backward call.

    FLOPs: QK^T and PV forward, and dQ, dK, dV, dP backward, each
    2*b*nq*s*s*hd halved by the causal mask (no recomputation counted).
    Bytes: each tensor read or written once at ``itemsize`` bytes: forward
    reads q, k, v and writes o; backward reads q, k, v, o, do and writes
    dq, dk, dv; the fp32 row statistic (b*nq*s) is written once and read
    once.
    """
    mm = 2.0 * b * nq * s * s * hd * 0.5
    flops = 6.0 * mm
    q_bytes = b * s * nq * hd * itemsize
    kv_bytes = b * s * nkv * hd * itemsize
    fwd = 2 * q_bytes + 2 * kv_bytes                      # q, o ; k, v
    bwd = 3 * q_bytes + 2 * kv_bytes + q_bytes + 2 * kv_bytes  # q,o,do,k,v ; dq,dk,dv
    lse = 2 * b * nq * s * 4
    return flops, float(fwd + bwd + lse)
