"""stage_device_ms (ms): device time per step of the pipeline stages'
forward and backward executables. The program jits each stage's
``make_stage_fn`` closure, named ``fn``, so its executables (forward and
the backward of its ``jax.vjp``) run as ``jit_fn``."""

STAGE_MODULES = ("jit_fn",)


def read(ctx):
    tm = ctx["trace_mod"]
    t = [tm.module_time(ctx["trace"], d, ctx["lo"], ctx["hi"],
                        lambda name: name in STAGE_MODULES)
         for d in ctx["devices"]]
    if not any(t):
        return None
    return 1e3 * sum(t) / len(t) / ctx["steps"]
