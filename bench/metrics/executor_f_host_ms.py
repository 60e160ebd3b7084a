"""executor_f_host_ms (ms): host time per step in the program's
``pipeline.F`` spans: the stage forward's ``jax.vjp`` trace and dispatch,
one span per F instruction."""

SPANS = ("pipeline.F",)


def read(ctx):
    tm = ctx["trace_mod"]
    spans = ctx["trace"].spans("pipeline.F")
    if not spans:
        return None
    t = tm.length(tm.union(((e.start, e.end) for e in spans),
                           ctx["lo"], ctx["hi"]))
    return t / 1e6 / ctx["steps"]
