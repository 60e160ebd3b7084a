"""interp_host_ms (ms): host time per step of the schedule interpreter:
each ``executor.step`` span (the benchmark's, around
``PipelineExecutor.step``) less the union of the program's ``pipeline.*``
spans inside it. That remainder is the ``plan.run`` ready loop, the plan
cache lookup, micro-batch slicing, store bookkeeping and the cap asserts.
``PIPELINE`` names every span the program opens in a step."""

#: The executor's release/restore ops, each with a ``pipeline.<op>`` span.
MOVES = ("EVICT", "LOAD", "OFFLOAD", "FETCH", "DROP", "RECOMPUTE")
PIPELINE = (("pipeline.split", "pipeline.merge", "pipeline.F", "pipeline.B",
             "pipeline.grad_accum") + tuple(f"pipeline.{op}" for op in MOVES))
SPANS = ("executor.step",) + PIPELINE


def read(ctx):
    tm, tr, lo, hi = ctx["trace_mod"], ctx["trace"], ctx["lo"], ctx["hi"]
    inner = [e for name in PIPELINE for e in tr.spans(name)]
    steps = [e for e in tr.spans("executor.step") if lo <= e.start < hi]
    if not inner or not steps:
        return None
    program = tm.union(((e.start, e.end) for e in inner), lo, hi)
    rest = tm.subtract(tm.union(((e.start, e.end) for e in steps), lo, hi),
                       program)
    return tm.length(rest) / 1e6 / ctx["steps"]
