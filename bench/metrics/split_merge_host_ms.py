"""split_merge_host_ms (ms): host time per step in the program's
``pipeline.split`` (stacked parameters sliced into stage parameters) and
``pipeline.merge`` (loss sum, stage gradients restacked) spans."""

SPANS = ("pipeline.split", "pipeline.merge")


def read(ctx):
    tm = ctx["trace_mod"]
    spans = [e for name in SPANS for e in ctx["trace"].spans(name)]
    if not spans:
        return None
    t = tm.length(tm.union(((e.start, e.end) for e in spans),
                           ctx["lo"], ctx["hi"]))
    return t / 1e6 / ctx["steps"]
