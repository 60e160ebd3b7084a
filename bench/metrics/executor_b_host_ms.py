"""executor_b_host_ms (ms): host self time per step of the program's
``pipeline.B`` spans (the stage backward's ``vjp_fn`` call and dispatch):
their length less the ``pipeline.grad_accum`` spans inside them."""

SPANS = ("pipeline.B", "pipeline.grad_accum")


def read(ctx):
    tm, tr, lo, hi = ctx["trace_mod"], ctx["trace"], ctx["lo"], ctx["hi"]
    b = tr.spans("pipeline.B")
    if not b:
        return None
    accum = tm.union(((e.start, e.end) for e in tr.spans("pipeline.grad_accum")),
                     lo, hi)
    own = tm.subtract(tm.union(((e.start, e.end) for e in b), lo, hi), accum)
    return tm.length(own) / 1e6 / ctx["steps"]
