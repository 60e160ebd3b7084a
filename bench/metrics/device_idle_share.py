"""device_idle_share (%): the share of the traced window in which no
operation ran on a chip, averaged over the cell's chips."""


def read(ctx):
    tm, tr, lo, hi = ctx["trace_mod"], ctx["trace"], ctx["lo"], ctx["hi"]
    idle = [1.0 - tm.length(tm.busy(tr, d, lo, hi)) / (hi - lo)
            for d in ctx["devices"]]
    return 100.0 * sum(idle) / len(idle)
