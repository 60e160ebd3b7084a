"""mfu (%): model FLOPs of the traced steps (``bench/flops.py``: matmuls of
forward and backward, no recomputation) over the traced window's seconds
times the chips times the chip's bf16 peak."""


def read(ctx):
    job = ctx["job"]
    work = ctx["flops"].model_flops_train(ctx["cfg"], job["rows"],
                                          job["seq"]) * ctx["steps"]
    secs = (ctx["hi"] - ctx["lo"]) / 1e9
    return 100.0 * work / (secs * len(ctx["devices"])
                           * ctx["peaks"]["bf16_flops_per_s"])
