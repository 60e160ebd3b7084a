"""The host-split reducers (``bench/metrics/executor_f_host_ms.py`` and
its siblings), which read the program's ``pipeline.*`` profiler spans: on
a synthetic trace worked out by hand, and on ``data/tiny.xplane.pb``,
which holds none of them. ``bench/host_split.py`` end to end on the CPU
at a tiny size."""
import jax
import pytest

from bench import harness, host_split, trace as tm
from bench.trace import Event, Trace
from test_trace import DATA, MS, SYNTH, _ctx, _op
from tiny import PEAKS, tiny_cell

SPLIT = host_split.SPLIT
PIPELINE = harness._load_file("metrics", "interp_host_ms").PIPELINE


def _split_names():
    return tuple(sorted(set().union(
        *(harness._load_file("metrics", m).SPANS for m in SPLIT))))


def _host(a, b, name):
    return Event(a * MS, b * MS, name)


# Window [0, 20] ms, two steps. Step 1 [1, 11]: split [1,2], F [2,4],
# B [4,7] holding grad_accum [5,6], EVICT [7,7.5], F [8,9], merge
# [10,10.5]; the interpreter has [7.5,8], [9,10] and [10.5,11]. Step 2
# [12, 14]: F [12,13], the interpreter [13,14]. Chip ops at [2.5,3] and
# [5.2,5.5].
PROGRAM = Trace(
    ops={0: [_op(2.5, 3), _op(5.2, 5.5)]}, modules={0: []},
    host=[_host(0, 20, "window"), _host(1, 11, "executor.step"),
          _host(1, 2, "pipeline.split"), _host(2, 4, "pipeline.F"),
          _host(4, 7, "pipeline.B"), _host(5, 6, "pipeline.grad_accum"),
          _host(7, 7.5, "pipeline.EVICT"), _host(8, 9, "pipeline.F"),
          _host(10, 10.5, "pipeline.merge"), _host(12, 14, "executor.step"),
          _host(12, 13, "pipeline.F")])


def test_host_split_reducers_by_hand():
    ctx = _ctx(PROGRAM, 0, 20 * MS, steps=2)
    read = lambda m: harness._load_file("metrics", m).read(ctx)  # noqa: E731
    # per step: F 1+2+1 = 4 ms; B 3 less its 1-ms accumulation; split
    # 1 and merge 0.5; the interpreter 0.5+1+0.5 in step 1 and 1 in step 2
    assert read("executor_f_host_ms") == pytest.approx(2.0)
    assert read("executor_b_host_ms") == pytest.approx(1.0)
    assert read("grad_accum_host_ms") == pytest.approx(0.5)
    assert read("split_merge_host_ms") == pytest.approx(0.75)
    assert read("interp_host_ms") == pytest.approx(1.5)
    # with the EVICT's 0.25 ms a step they add up to the executor's time
    assert sum(read(m) for m in SPLIT) + 0.25 == pytest.approx(
        read("executor_host_ms"))
    # idle gaps named by the innermost program span they begin in:
    # [0,2.5] before any, [3,5.2] in F, [5.5,20] in grad_accum (inside B)
    gaps = tm.idle_gaps(PROGRAM, 0, 0, 20 * MS, PIPELINE)
    assert gaps == [["pipeline.grad_accum", pytest.approx(14.5e-3)],
                    ["other", pytest.approx(2.5e-3)],
                    ["pipeline.F", pytest.approx(2.2e-3)]]


def test_host_split_absent_without_program_spans():
    """A program that opens no ``pipeline.*`` span (such as one from
    before them) gives none of the host-split metrics."""
    for tr in (SYNTH, tm.load(str(DATA.parent), harness.HOST_SPANS
                              + _split_names())):
        (win,) = tr.spans("window")
        ctx = _ctx(tr, win.start, win.end, steps=3)
        for m in SPLIT:
            assert harness._load_file("metrics", m).read(ctx) is None, m


def test_recorded_tiny_trace_unchanged_by_the_split_names():
    """Loading the program's span names too leaves every accepted metric
    and the idle gaps' labels as they read without them."""
    old = tm.load(str(DATA.parent), harness.HOST_SPANS)
    new = tm.load(str(DATA.parent), harness.HOST_SPANS + _split_names())
    (win,) = old.spans("window")
    lo, hi = win.start, win.end

    def readings(tr):
        ctx = _ctx(tr, lo, hi, steps=3)
        return ({m: harness._load_file("metrics", m).read(ctx)
                 for m in ("device_idle_share", "mfu", "executor_host_ms",
                           "stage_device_ms", "flash_roofline",
                           "adam_device_ms")},
                tm.idle_gaps(tr, 0, lo, hi, harness.HOST_SPANS[1:]))

    assert readings(new) == readings(old)
    assert readings(old)[0]["executor_host_ms"] > 0


def test_host_split_end_to_end_on_cpu():
    """The tool on a tiny cell: its parts add up to the executor's time,
    and every idle second of the window is put down to a span or
    ``other`` (the CPU trace has no chip plane, so the chip is idle)."""
    cell = tiny_cell()
    lines = []
    r = host_split.split(cell, 2**33 + 29, 2, jax.devices()[:1],
                         log=lines.append, peaks=PEAKS)
    m = r["metrics"]
    assert all(m[name] > 0 for name in SPLIT), m
    assert r["parts_over_executor_host"] == pytest.approx(1.0, rel=1e-6)
    p, mb = cell.job["schedule"]["p"], cell.job["schedule"]["m"]
    assert r["spans_per_step"]["pipeline.F"] == p * mb
    assert r["spans_per_step"]["pipeline.split"] == 1
    assert r["busy_s"] == 0 and r["custom_calls"] == {}
    assert sum(r["idle_s_by_pipeline_span"].values()) == pytest.approx(
        r["window_s"])
    assert set(r["step_s"]) == {"untraced_before", "traced", "untraced_after"}
    assert host_split.span_cost_us(1000) > 0
