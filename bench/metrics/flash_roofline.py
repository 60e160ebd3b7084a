"""flash_roofline (%): the least time the chip could take for the traced
steps' causal attention (the larger of its FLOPs over the bf16 peak and
its bytes over the HBM bandwidth, ``bench/flops.flash_attention_work``)
over the device time of the flash-attention kernels.

The kernels are the ``custom-call`` operations: the program's only custom
calls are its Pallas kernels, and with ``attn_impl: flash`` those are the
flash forward, dq and dk/dv kernels."""


def read(ctx):
    tm, cfg, job = ctx["trace_mod"], ctx["cfg"], ctx["job"]
    secs = sum(tm.op_time(ctx["trace"], d, ctx["lo"], ctx["hi"],
                          lambda name: tm.opcode(name) == "custom-call")
               for d in ctx["devices"])
    if secs <= 0:
        return None
    f, b = ctx["flops"].flash_attention_work(
        job["micro_batch"], job["seq"], cfg.num_heads, cfg.num_kv_heads,
        cfg.head_dim)
    calls = cfg.num_layers * (job["rows"] // job["micro_batch"]) * ctx["steps"]
    least = calls * max(f / ctx["peaks"]["bf16_flops_per_s"],
                        b / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / secs
