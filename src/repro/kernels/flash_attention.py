"""Flash-attention (forward and two-pass backward) as Pallas TPU kernels.

TPU adaptation of flash-attention-2: instead of
warp-level tiling in SRAM, q/k/v tiles live in VMEM via BlockSpec, the
score matmul feeds the 128x128 MXU (block sizes default to 128), and the
online-softmax running max/denominator accumulate in fp32 VMEM scratch
across the ``arbitrary``-ordered kv grid dimension.

Layout: the kernels see heads-major operands — q/o/do as
(b, nkv, m, s, hd) and k/v as (b, nkv, s, hd) — so every block's last two
dims are (sequence tile, head_dim), which the TPU lowering accepts
(second-minor a multiple of 8, minor the whole head_dim). Per-row
statistics (running max, denominator, LSE, delta) are carried as
lane-replicated (rows, 128) tiles for the same reason. The public
functions take and return the model's (b, s, heads, hd) layout and
transpose at the boundary.

GQA: the m = nq // nkv query heads sharing one kv head are stacked into
the q tile's rows (m * block_q of them), so one kv tile is loaded once
per m queries (the same reuse flash-attention-2 gets from its head
grouping).

Supports: causal masking, sliding-window (local) masking, gemma2-style
logit softcap, a ``q_offset`` for sequence-sliced attention, and a value
head narrower or wider than the query/key head (MLA: q/k 192, v 128);
the output and dv take v's width, dq and dk q/k's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(np.finfo(np.float32).max)
LANES = 128  # row statistics are replicated across one vreg's lanes
_PARALLEL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _relevant(qi, ki, *, causal, window, block_q, block_k, kv_len,
              q_offset=0, **_):
    """Block-level sparsity (masking inside a dense op never saves work —
    skipping blocks does):
      causal: kv blocks strictly above the diagonal contribute nothing;
      window: kv blocks whose newest key is older than the oldest
              query's horizon contribute nothing.
    q_offset shifts query positions by the retained-KV prefix length
    (sequence-sliced schedules: slice queries start at global position
    q_offset while keys cover [0, kv_len))."""
    rel = ki * block_k < kv_len
    if causal:  # oldest query in this q tile vs newest key in kv tile
        rel &= ki * block_k <= qi * block_q + q_offset + block_q - 1
    if window:
        rel &= (ki + 1) * block_k - 1 > qi * block_q + q_offset - window
    return rel


def _scores(q, k, qi, ki, *, m, scale, causal, window, softcap, block_q,
            block_k, kv_len, q_offset=0):
    """Masked (m*bq, bk) scores of a q tile against a kv tile, plus the
    softcap chain factor d(softcap(s))/ds (1.0 without softcap).

    q: (m*bq, hd) — m query heads' rows stacked head-major; k: (bk, hd).
    """
    rows, bk = q.shape[0], k.shape[0]
    s = jax.lax.dot_general(
        q.astype(jnp.float32), k.astype(jnp.float32),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    dcap = 1.0
    if softcap:
        t = jnp.tanh(s / softcap)
        s = softcap * t
        dcap = 1.0 - t * t
    qpos = qi * block_q + q_offset + jax.lax.broadcasted_iota(
        jnp.int32, (m, rows // m, bk), 1).reshape(rows, bk)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
    mask = kpos < kv_len                        # kv padding
    if causal:
        mask &= qpos >= kpos
    if window:
        mask &= qpos - kpos < window
    return jnp.where(mask, s, NEG_INF), dcap


def _rows(ref):
    """A (1, 1, m, bq, x) block as its (m*bq, x) row matrix."""
    m, bq, x = ref.shape[2:]
    return ref[0, 0].reshape(m * bq, x)


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
            nkv_blocks, **common):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(_relevant(qi, ki, **common))
    def _block():
        s, _ = _scores(_rows(q_ref), k_ref[0, 0], qi, ki,
                       m=q_ref.shape[2], **common)
        m_prev = m_scr[...]                                  # (rows, LANES)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])                        # (rows, bk)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (rows, hd)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + pv
        m_scr[...] = m_new

    @pl.when(ki == nkv_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom[:, :1]).reshape(
            o_ref.shape[2:]).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[...] + jnp.log(denom)).reshape(
            lse_ref.shape[2:])


def _prepare(q, k, v, block_q, block_k):
    """Heads-major, tile-padded operands and the static tiling facts."""
    b, sq, nq, hd = q.shape
    sk, nkv = k.shape[1], k.shape[2]
    assert nq % nkv == 0
    m = nq // nkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    qh = _pad_seq(_heads_major_q(q, nkv), pad_q, axis=3)
    kh = _pad_seq(k.transpose(0, 2, 1, 3), pad_k, axis=2)
    vh = _pad_seq(v.transpose(0, 2, 1, 3), pad_k, axis=2)
    return qh, kh, vh, dict(b=b, sq=sq, sk=sk, nkv=nkv, m=m, hd=hd,
                            hd_v=v.shape[3],
                            block_q=block_q, block_k=block_k,
                            nq_blocks=(sq + pad_q) // block_q,
                            nkv_blocks=(sk + pad_k) // block_k)


def _heads_major_q(x, nkv):
    """(b, s, nkv*m, hd) -> (b, nkv, m, s, hd)."""
    b, s, n, hd = x.shape
    return x.reshape(b, s, nkv, n // nkv, hd).transpose(0, 2, 3, 1, 4)


def _seq_major_q(x, sq):
    """(b, nkv, m, s_p, hd) -> (b, sq, nkv*m, hd), dropping q padding."""
    b, g, m, _, hd = x.shape
    return x[:, :, :, :sq].transpose(0, 3, 1, 2, 4).reshape(b, sq, g * m, hd)


def _pad_seq(x, pad, axis):
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _row_stat(x, nkv, pad_q):
    """(b, sq, nkv, m) row statistic -> lane-replicated, tile-padded
    (b, nkv, m, sq_p, LANES), the layout the backward kernels read."""
    x = _pad_seq(x.astype(jnp.float32).transpose(0, 2, 3, 1), pad_q, axis=3)
    return jnp.broadcast_to(x[..., None], x.shape + (LANES,))


def flash_attention_fwd(q, k, v, *, causal=True, window=0, softcap=0.0,
                        scale=None, block_q=128, block_k=128,
                        interpret=False, return_lse=False, q_offset=0):
    """q: (b, sq, nq, hd); k: (b, sk, nkv, hd); v: (b, sk, nkv, hd_v).
    Returns (b, sq, nq, hd_v) and, with ``return_lse``, the
    (b, sq, nkv, m) log-sum-exp rows. The scale defaults to
    1/sqrt(hd).

    ``q_offset`` shifts the queries' positions for the causal/window
    masks: query row i is at global position i + q_offset while keys
    cover [0, sk) — the sequence-sliced case where the kv side carries a
    retained prefix of q_offset earlier keys (docs/longcontext.md).
    """
    qh, kh, vh, t = _prepare(q, k, v, block_q, block_k)
    b, nkv, m, hd, hd_v = (t[x] for x in ("b", "nkv", "m", "hd", "hd_v"))
    bq, bk = t["block_q"], t["block_k"]
    sq_p = qh.shape[3]
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=bq, block_k=bk,
        nkv_blocks=t["nkv_blocks"], kv_len=t["sk"], q_offset=q_offset)
    q_map = lambda bb, g, qi, ki: (bb, g, 0, qi, 0)  # noqa: E731
    kv_map = lambda bb, g, qi, ki: (bb, g, ki, 0)  # noqa: E731
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, nkv, t["nq_blocks"], t["nkv_blocks"]),
        in_specs=[pl.BlockSpec((1, 1, m, bq, hd), q_map),
                  pl.BlockSpec((1, 1, bk, hd), kv_map),
                  pl.BlockSpec((1, 1, bk, hd_v), kv_map)],
        out_specs=[pl.BlockSpec((1, 1, m, bq, hd_v), q_map),
                   pl.BlockSpec((1, 1, m, bq, LANES), q_map)],
        out_shape=[
            jax.ShapeDtypeStruct(qh.shape[:4] + (hd_v,), q.dtype),
            jax.ShapeDtypeStruct((b, nkv, m, sq_p, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((m * bq, LANES), jnp.float32),
            pltpu.VMEM((m * bq, LANES), jnp.float32),
            pltpu.VMEM((m * bq, hd_v), jnp.float32),
        ],
        compiler_params=_PARALLEL,
        interpret=interpret,
        name="flash_fwd",
    )(qh, kh, vh)
    out = _seq_major_q(out, t["sq"])
    if return_lse:
        return out, lse[:, :, :, :t["sq"], 0].transpose(0, 3, 1, 2)
    return out


# ---------------------------------------------------------------------------
# Backward kernels (flash-attention-2 style two-pass)
# ---------------------------------------------------------------------------
def _probs_and_dscores(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, qi, ki,
                       common):
    """Recompute the (rows, bk) probability tile from the saved LSE and
    the score cotangent ds = p * (dp - delta) * dcap * scale."""
    scale = common["scale"]
    q, do = _rows(q_ref), _rows(do_ref).astype(jnp.float32)
    s, dcap = _scores(q, k_ref[0, 0], qi, ki, m=q_ref.shape[2], **common)
    p = jnp.exp(s - _rows(lse_ref)[:, :1])   # masked entries -> 0
    dp = jax.lax.dot_general(
        do, v_ref[0, 0].astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)  # (rows, bk)
    ds = p * (dp - _rows(dlt_ref)[:, :1]) * dcap * scale
    return q, do, p, ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dq_ref,
               acc_scr, *, nkv_blocks, **common):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(_relevant(qi, ki, **common))
    def _block():
        _, _, _, ds = _probs_and_dscores(q_ref, k_ref, v_ref, do_ref,
                                         lse_ref, dlt_ref, qi, ki, common)
        acc_scr[...] += jax.lax.dot_general(
            ds, k_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nkv_blocks - 1)
    def _finish():
        dq_ref[0, 0] = acc_scr[...].reshape(dq_ref.shape[2:]).astype(
            dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, nq_blocks, **common):
    ki, qi = pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(_relevant(qi, ki, **common))
    def _block():
        q, do, p, ds = _probs_and_dscores(q_ref, k_ref, v_ref, do_ref,
                                          lse_ref, dlt_ref, qi, ki, common)
        # dv += p^T do, dk += ds^T q   (sum over the m*bq rows)
        tn = (((0,), (0,)), ((), ()))
        dv_scr[...] += jax.lax.dot_general(
            p, do, tn, preferred_element_type=jnp.float32)
        dk_scr[...] += jax.lax.dot_general(
            ds, q.astype(jnp.float32), tn,
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq_blocks - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, out, lse, dout, *, causal=True, window=0,
                        softcap=0.0, scale=None, block_q=128, block_k=128,
                        interpret=False, q_offset=0):
    """dq, dk, dv via the two-pass flash backward.

    q: (b, sq, nq, hd); k: (b, sk, nkv, hd); v: (b, sk, nkv, hd_v);
    out/dout: (b, sq, nq, hd_v); lse: (b, sq, nkv, m) from the forward.
    ``q_offset`` as in the fwd.
    """
    qh, kh, vh, t = _prepare(q, k, v, block_q, block_k)
    b, sq, sk, nkv, m, hd, hd_v = (t[x] for x in ("b", "sq", "sk", "nkv",
                                                  "m", "hd", "hd_v"))
    bq, bk = t["block_q"], t["block_k"]
    pad_q = qh.shape[3] - sq
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(b, sq, nkv, m)   # D = rowsum(do*o)
    doh = _pad_seq(_heads_major_q(dout, nkv), pad_q, axis=3)
    operands = (qh, kh, vh, doh, _row_stat(lse, nkv, pad_q),
                _row_stat(delta, nkv, pad_q))
    common = dict(scale=scale, causal=causal, window=window, softcap=softcap,
                  block_q=bq, block_k=bk, kv_len=sk, q_offset=q_offset)

    # Index maps differ between the two passes (which tile index is the
    # reduction axis); both see the same operand tuple.
    def specs(q_map, kv_map):
        qs = pl.BlockSpec((1, 1, m, bq, hd), q_map)
        kvs = pl.BlockSpec((1, 1, bk, hd), kv_map)
        vs = pl.BlockSpec((1, 1, bk, hd_v), kv_map)
        dos = pl.BlockSpec((1, 1, m, bq, hd_v), q_map)
        stat = pl.BlockSpec((1, 1, m, bq, LANES), q_map)
        return [qs, kvs, vs, dos, stat, stat], qs, kvs, vs

    # --- pass 1: dq; grid (b, nkv, q_blocks, kv_blocks[arbitrary]) ----------
    in_specs, q_spec, _, _ = specs(lambda bb, g, qi, ki: (bb, g, 0, qi, 0),
                                   lambda bb, g, qi, ki: (bb, g, ki, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, nkv_blocks=t["nkv_blocks"], **common),
        grid=(b, nkv, t["nq_blocks"], t["nkv_blocks"]),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((m * bq, hd), jnp.float32)],
        compiler_params=_PARALLEL,
        interpret=interpret,
        name="flash_bwd_dq",
    )(*operands)

    # --- pass 2: dk/dv; grid (b, nkv, kv_blocks, q_blocks[arbitrary]) -------
    in_specs, _, kv_spec, v_spec = specs(
        lambda bb, g, ki, qi: (bb, g, 0, qi, 0),
        lambda bb, g, ki, qi: (bb, g, ki, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, nq_blocks=t["nq_blocks"], **common),
        grid=(b, nkv, t["nkv_blocks"], t["nq_blocks"]),
        in_specs=in_specs,
        out_specs=[kv_spec, v_spec],
        out_shape=[jax.ShapeDtypeStruct(kh.shape, k.dtype),
                   jax.ShapeDtypeStruct(vh.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hd_v), jnp.float32)],
        compiler_params=_PARALLEL,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*operands)

    return (_seq_major_q(dq, sq), dk[:, :, :sk].transpose(0, 2, 1, 3),
            dv[:, :, :sk].transpose(0, 2, 1, 3))
