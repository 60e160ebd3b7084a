"""moe_expert_roofline (%): the least time the chip could take for the
traced steps' grouped expert matmuls (the larger of their FLOPs over the
bf16 peak and their bytes over the HBM bandwidth, the architecture
module's ``expert_work``) over the device time of the grouped-matmul
kernels.

The work is counted at the picks that land on the held experts, as the
``executor_routing`` entry counted them in set-up (``job["routing"]``:
the reference's routing of the first batch on the seed's weights), spread
evenly over the MoE layers and micro-batches, and with no recomputation:
the forward matmuls that the program reruns in the backward (it
recomputes a dropless MoE's FFN) are in the device time, not in the work.

The kernels are the ``custom-call`` operations whose instruction name
holds ``gmm``: JAX's megablox kernels ``gmm`` (forward and input
gradient) and ``tgmm`` (weight gradient), which the program's
``kernels.ops.grouped_matmul`` runs. A module without ``expert_work``, or
a job with no routing count, reads nothing."""


def is_gmm(tm, name):
    return tm.opcode(name) == "custom-call" and "gmm" in name.split(" = ")[0]


def read(ctx):
    tm, job, model = ctx["trace_mod"], ctx["job"], ctx["model"]
    if not hasattr(model, "expert_work") or "routing" not in job:
        return None
    secs = sum(tm.op_time(ctx["trace"], d, ctx["lo"], ctx["hi"],
                          lambda name: is_gmm(tm, name))
               for d in ctx["devices"]) / len(ctx["devices"])
    if secs <= 0:
        return None
    sizes = ctx["sizes"]
    moe_layers = sum(k == "moe" for k in model.layer_kinds(sizes))
    per_step = moe_layers * (job["rows"] // job["micro_batch"])
    rows = job["routing"]["held_picks"] / per_step
    f, b = model.expert_work(sizes, job["micro_batch"], job["seq"],
                             rows=rows)
    least = per_step * ctx["steps"] * max(
        f / ctx["peaks"]["bf16_flops_per_s"],
        b / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / secs
