"""grad_accum_host_ms (ms): host time per step in the program's
``pipeline.grad_accum`` spans: adding each B's stage gradients to the
step's running sum, one jitted call per B that donates the sum."""

SPANS = ("pipeline.grad_accum",)


def read(ctx):
    tm = ctx["trace_mod"]
    spans = ctx["trace"].spans("pipeline.grad_accum")
    if not spans:
        return None
    t = tm.length(tm.union(((e.start, e.end) for e in spans),
                           ctx["lo"], ctx["hi"]))
    return t / 1e6 / ctx["steps"]
