"""Jit-ready public wrappers for the Pallas kernels.

``flash_attention``: Pallas forward + Pallas two-pass backward (dq and
dk/dv kernels recomputing probabilities from the saved LSE — the
flash-attention-2 scheme).
``fused_softmax``: Pallas forward and backward kernels via custom_vjp.
``grouped_matmul``: the rows of each group times that group's matrix, as
JAX's megablox Pallas kernels (``gmm`` forward and input gradient,
``tgmm`` weight gradient), over the groups a layer holds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental.pallas.ops.tpu.megablox import ops as _megablox

from repro.kernels import fused_softmax as _fs
from repro.kernels import ref as _ref
from repro.kernels.flash_attention import (flash_attention_bwd,
                                           flash_attention_fwd)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def flash_attention(q, k, v, causal=True, window=0, softcap=0.0,
                    scale=None, block_q=128, block_k=128, interpret=False,
                    q_offset=0):
    """``q_offset`` shifts query positions for the causal/window masks
    (sequence-sliced attention over a retained-KV prefix of that many
    keys — docs/longcontext.md). 0 is plain full-sequence attention."""
    return flash_attention_fwd(
        q, k, v, causal=causal, window=window, softcap=softcap,
        scale=scale, block_q=block_q, block_k=block_k, interpret=interpret,
        q_offset=q_offset)


def _fa_fwd(q, k, v, causal, window, softcap, scale, block_q, block_k,
            interpret, q_offset):
    out, lse = flash_attention_fwd(
        q, k, v, causal=causal, window=window, softcap=softcap,
        scale=scale, block_q=block_q, block_k=block_k, interpret=interpret,
        return_lse=True, q_offset=q_offset)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, window, softcap, scale, block_q, block_k, interpret,
            q_offset, res, g):
    q, k, v, out, lse = res
    return flash_attention_bwd(
        q, k, v, out, lse, g, causal=causal, window=window, softcap=softcap,
        scale=scale, block_q=block_q, block_k=block_k, interpret=interpret,
        q_offset=q_offset)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def fused_softmax(x, scale=1.0, causal=False, block_rows=256,
                  interpret=False):
    """x: (..., sq, sk) attention scores; fused upcast+scale+mask+softmax."""
    return _fs_apply(x, scale, causal, block_rows, interpret)


def _fs_apply(x, scale, causal, block_rows, interpret):
    *lead, sq, sk = x.shape
    if causal:
        assert sq == sk, "causal fused softmax expects square scores"
    rows = 1
    for d in lead + [sq]:
        rows *= d
    br = min(block_rows, rows)
    while rows % br:
        br -= 1
    y = _fs.fused_softmax_fwd(x.reshape(rows, sk), scale=scale,
                              causal=causal, block_rows=br,
                              interpret=interpret)
    return y.reshape(x.shape)


def _fsm_fwd(x, scale, causal, block_rows, interpret):
    y = _fs_apply(x, scale, causal, block_rows, interpret)
    return y, y


def _fsm_bwd(scale, causal, block_rows, interpret, y, g):
    *lead, sq, sk = y.shape
    rows = 1
    for d in lead + [sq]:
        rows *= d
    br = min(block_rows, rows)
    while rows % br:
        br -= 1
    dx = _fs.fused_softmax_bwd(y.reshape(rows, sk), g.reshape(rows, sk),
                               scale=scale, block_rows=br,
                               interpret=interpret)
    return (dx.reshape(y.shape),)


fused_softmax.defvjp(_fsm_fwd, _fsm_bwd)


def unfused_softmax_chain(x, scale=1.0, causal=False):
    """The paper's exp-(7) *unfused* chain, staged as separate ops (upcast,
    scale, mask, softmax, downcast) — the baseline kernel_bench compares
    the fused kernel against."""
    xf = x.astype(jnp.float32)
    xf = xf * scale
    if causal:
        sq, sk = x.shape[-2:]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        xf = jnp.where(mask, xf, _ref.NEG_INF)
    y = jax.nn.softmax(xf, axis=-1)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
def _gmm_tiling(m, k, n):
    """Tile sizes (rows, contraction, output columns) of a grouped matmul
    whose lhs has m rows: row tiles divide m; a partial last contraction
    or column tile is masked by the kernel."""
    tm = next((t for t in (512, 256, 128, 64, 32, 16, 8) if m % t == 0), m)
    return tm, min(k, 512), min(n, 1024)


def grouped_matmul(lhs, rhs, group_sizes, group_offset=0, interpret=False):
    """lhs (m, k) rows sorted by group, ``group_sizes`` (G,) int32 rows per
    group; rhs (H, k, n) the matrices of groups [group_offset,
    group_offset + H). Returns (m, n) in lhs's dtype: each held group's
    rows times its matrix. Rows of groups not held are not computed and
    hold no defined value; the caller masks them."""
    return _megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, _gmm_tiling,
                         jnp.asarray(group_offset, jnp.int32), None, False,
                         interpret)
