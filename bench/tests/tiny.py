"""A cell cut to a size the CPU runs in seconds, for the harness tests."""
import copy

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


def tiny_cell(name="qwen1.5-0.5b-untied.bpipe-flash", model=None, job=None):
    cell = harness.find_cell(name)
    cell.config = copy.deepcopy(cell.config)
    cell.config["model"].update(TINY_MODEL, **(model or {}))
    cell.job = {**copy.deepcopy(cell.job), **TINY_JOB, **(job or {})}
    cell.limits = dict(TINY_LIMITS)
    return cell
