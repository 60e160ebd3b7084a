"""Cells cut to a size the CPU runs in seconds, for the harness tests."""
import copy

import moe_ref
from bench import harness

TINY_MODEL = {"num_layers": 4, "d_model": 128, "num_heads": 4,
              "num_kv_heads": 4, "head_dim": 32, "d_ff": 256,
              "vocab_size": 512}
TINY_JOB = {"rows": 4, "seq": 128, "schedule": {"kind": "bpipe", "p": 4,
                                                 "m": 4}}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
#: Limits at this size, from ``bench/control.py``'s readings on the CPU
#: (13 seeds of the program, 3 of each variant): lower = the program's
#: largest, upper = the least control reading (loss_gap 2.33e-3,
#: grad_gap 5.01e-2) or fault reading (update_gap, half_batch: 0.185).
#: The program read 7.3e-4, 4.9e-3 and 1.26e-2 at most.
TINY_LIMITS = {"loss_gap": 1.4e-3, "grad_gap": 1.6e-2, "update_gap": 4.5e-2,
               "window_compiles": 0}
#: The program's MoE block (``models/moe.py``, 4 experts top-2, no token
#: dropped at capacity factor 4/2) under alternating windowed and global
#: attention, checked against ``moe_ref``.
TINY_MOE_CONFIG = {
    "base": "granite-moe-1b-a400m", "reference": "moe_ref",
    "model": {"num_layers": 4, "d_model": 128, "num_heads": 4,
              "num_kv_heads": 4, "head_dim": 32, "d_ff": 0,
              "vocab_size": 512, "block_pattern": ["local_attn", "attn"],
              "window_size": 16, "qkv_bias": False, "tie_embeddings": False,
              "rope_theta": 10000.0,
              "moe": {"num_experts": 4, "top_k": 2, "d_ff": 128,
                      "capacity_factor": 2.0, "router_aux_weight": 0.01}}}

#: Limits of that cell, from ``bench/control.py``'s readings on the CPU:
#: the program over 13 seeds read at most loss_gap 9.85e-4, grad_gap
#: 7.95e-2 (one seed; the router's gradient, a sum over tokens that
#: cancels, computed in bfloat16) and update_gap 4.46e-3; with the gates
#: left unnormalised (3 seeds) at least 1.94e-3, 0.207 and 3.15e-2.
TINY_MOE_LIMITS = {"loss_gap": 1.4e-3, "grad_gap": 0.14, "update_gap": 1.5e-2,
                   "window_compiles": 0}


def tiny_cell(name="qwen1.5-0.5b-untied.bpipe-flash", model=None, job=None):
    cell = harness.find_cell(name)
    cell.config = copy.deepcopy(cell.config)
    cell.config["model"].update(TINY_MODEL, **(model or {}))
    cell.job = {**copy.deepcopy(cell.job), **TINY_JOB, **(job or {})}
    cell.limits = dict(TINY_LIMITS)
    return cell


def tiny_moe_cell():
    """The tiny cell's job and limits on ``TINY_MOE_CONFIG``, whose
    reference is the test-only ``moe_ref``."""
    cell = tiny_cell()
    cell.name, cell.config, cell.arch = ("tiny-moe.bpipe-flash",
                                         copy.deepcopy(TINY_MOE_CONFIG),
                                         moe_ref)
    cell.limits = dict(TINY_MOE_LIMITS)
    return cell
