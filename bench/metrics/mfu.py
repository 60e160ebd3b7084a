"""mfu (%): model FLOPs of the traced steps (the cell's architecture
module's ``model_flops_train``: matmuls of forward and backward, no
recomputation) over the traced window's seconds times the chips times the
chip's bf16 peak."""


def read(ctx):
    job = ctx["job"]
    work = ctx["model"].model_flops_train(ctx["sizes"], job["rows"],
                                          job["seq"]) * ctx["steps"]
    secs = (ctx["hi"] - ctx["lo"]) / 1e9
    return 100.0 * work / (secs * len(ctx["devices"])
                           * ctx["peaks"]["bf16_flops_per_s"])
