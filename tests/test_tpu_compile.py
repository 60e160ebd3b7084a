"""Compiles for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode accepts: block shapes not
aligned to the (8, 128) tiling, kernels over the VMEM budget, programs
over the chip's HBM. These tests ask it about the main path at real
widths: the flash-attention kernels (forward and both backward passes)
at the registered head layouts, and one full-width executor stage.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import model as M
from repro.pipeline import stage as stage_mod

V5E_HBM_BYTES = 16 * 2**30
SEQ = 2048


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("nq,nkv,hd", [
    (16, 16, 64),     # qwen1.5-0.5b
    (16, 16, 96),     # gpt3-96b head_dim
    (16, 16, 128),    # llama-65b head_dim
    (40, 8, 128),     # qwen3-14b GQA
], ids=["hd64", "hd96", "hd128", "gqa40x8"])
def test_flash_attention_compiles_for_v5e(one_chip, nq, nkv, hd):
    q = jax.ShapeDtypeStruct((1, SEQ, nq, hd), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, SEQ, nkv, hd), jnp.bfloat16,
                              sharding=one_chip)

    def fwd_bwd(q, k, v):
        out, vjp = jax.vjp(ops.flash_attention, q, k, v)
        return out, vjp(out)

    txt = jax.jit(fwd_bwd).lower(q, kv, kv).compile().as_text()
    # forward, dq and dk/dv: three Pallas kernels, none left to XLA,
    # each under its own name in the compiled HLO
    assert txt.count("tpu_custom_call") >= 3
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert name in txt, name


def test_full_width_stage_fits_v5e_hbm(one_chip):
    """The last executor stage of qwen1.5-0.5b (6 layers, the 151,936-row
    tied unembedding and its fp32 logits) at micro-batch 1 x seq 1024,
    forward plus vjp with remat="attn", fits one chip's HBM."""
    cfg = get_config("qwen1.5-0.5b")
    p, last, seq = 4, 3, 1024
    fn = stage_mod.make_stage_fn(cfg, p, last, remat="attn")
    stage_params = jax.eval_shape(
        lambda k: stage_mod.StageSplitter(cfg, p).split(
            M.init_params(k, cfg))[last], jax.random.PRNGKey(0))
    carry = (jax.ShapeDtypeStruct((1, seq, cfg.d_model), jnp.bfloat16),
             jax.ShapeDtypeStruct((), jnp.float32))
    micro = {k: jax.ShapeDtypeStruct((1, seq), jnp.int32)
             for k in ("tokens", "labels")}

    def fwd_vjp(sp, carry, micro):
        loss, vjp = jax.vjp(fn, sp, carry, micro)
        return loss, vjp(jnp.ones_like(loss))[:2]

    compiled = jax.jit(fwd_vjp).lower(
        _on(one_chip, stage_params), _on(one_chip, carry),
        _on(one_chip, micro)).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, total
