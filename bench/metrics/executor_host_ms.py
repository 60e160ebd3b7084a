"""executor_host_ms (ms): host time per step inside
``PipelineExecutor.step``, from the benchmark's ``executor.step`` span."""


def read(ctx):
    spans = [e for e in ctx["trace"].spans("executor.step")
             if ctx["lo"] <= e.start < ctx["hi"]]
    if not spans:
        return None
    return sum(e.end - e.start for e in spans) / 1e6 / ctx["steps"]
