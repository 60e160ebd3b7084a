"""mla_flash_roofline (%): the least time the chip could take for the
traced steps' causal MLA attention (the larger of its FLOPs over the bf16
peak and its bytes over the HBM bandwidth, the architecture module's
``attention_work`` at q/k 192 and v 128) over the device time of the
flash-attention kernels.

The kernels are the ``custom-call`` operations whose instruction name
holds ``flash_``: the program's Pallas kernels are named ``flash_fwd``,
``flash_bwd_dq`` and ``flash_bwd_dkv``, and the names of its other
custom calls (the grouped matmuls ``gmm`` and ``tgmm``) do not hold it.
A module without ``attention_work`` reads nothing."""


def is_flash(tm, name):
    return tm.opcode(name) == "custom-call" and "flash_" in name.split(" = ")[0]


def read(ctx):
    tm, job, model = ctx["trace_mod"], ctx["job"], ctx["model"]
    if not hasattr(model, "attention_work"):
        return None
    secs = sum(tm.op_time(ctx["trace"], d, ctx["lo"], ctx["hi"],
                          lambda name: is_flash(tm, name))
               for d in ctx["devices"]) / len(ctx["devices"])
    if secs <= 0:
        return None
    f, b = model.attention_work(ctx["sizes"], job["micro_batch"], job["seq"])
    calls = (ctx["sizes"]["num_layers"] * (job["rows"] // job["micro_batch"])
             * ctx["steps"])
    least = calls * max(f / ctx["peaks"]["bf16_flops_per_s"],
                        b / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / secs
