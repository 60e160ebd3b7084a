"""adam_device_ms (ms): device time per step of the optimizer update, the
executable the entries jit as ``adam_update``."""


def read(ctx):
    tm = ctx["trace_mod"]
    t = [tm.module_time(ctx["trace"], d, ctx["lo"], ctx["hi"],
                        lambda name: name == "jit_adam_update")
         for d in ctx["devices"]]
    if not any(t):
        return None
    return 1e3 * sum(t) / len(t) / ctx["steps"]
