"""The trace reduction, checked by hand: on synthetic events whose busy
union, idle share and exposed share are worked out below, and on
``data/tiny.xplane.pb``, a trace of a tiny program recorded on one TPU v5e
by ``record_trace.py``."""
import pathlib
import types

import pytest

from bench import flops, harness, trace as tm
from bench.models import qwen2
from bench.trace import Event, Trace

DATA = pathlib.Path(__file__).resolve().parent / "data" / "tiny.xplane.pb"
MS = 1e6  # ns
SIZES = {"num_layers": 2, "num_heads": 4, "num_kv_heads": 4, "head_dim": 16,
         "d_model": 64, "d_ff": 128, "vocab_size": 512}


def _ctx(tr, lo, hi, steps=1, devices=(0,)):
    return {"trace": tr, "lo": lo, "hi": hi, "devices": list(devices),
            "steps": steps, "trace_mod": tm, "flops": flops,
            "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
            "cfg": types.SimpleNamespace(**SIZES), "model": qwen2,
            "sizes": SIZES, "job": {"rows": 2, "seq": 128, "micro_batch": 1}}


def _op(a, b, name="%fusion.1 = f32[8] fusion(f32[8] %p)"):
    return Event(a * MS, b * MS, name)


# Window [0, 10] ms. Ops: [1,3], [2,4] (overlap), [6,7], [6.5, 9].
SYNTH = Trace(
    ops={0: [_op(1, 3), _op(2, 4), _op(6, 7),
             _op(6.5, 9, "%all-reduce.2 = f32[8] all-reduce(f32[8] %x)"),
             _op(4.5, 5, "%k.1 = f32[8] custom-call(f32[8] %x)"),
             # a scan's loop op spans its body: neither busy nor compute
             _op(0.5, 9.5, "%while.3 = (s32[], f32[8]) while((s32[], f32[8]) %t)")]},
    modules={0: [Event(1 * MS, 4 * MS, "jit_fn(11)"),
                 Event(4.5 * MS, 5 * MS, "jit_fn(11)"),
                 Event(6 * MS, 9 * MS, "jit_adam_update(12)")]},
    host=[Event(0, 10 * MS, "window"), Event(0, 1 * MS, "batch"),
          Event(1 * MS, 6 * MS, "executor.step"),
          Event(6 * MS, 9.5 * MS, "adam.update"),
          Event(9.5 * MS, 10 * MS, "loss_read")])


def test_union_and_subtract():
    u = tm.union([(1, 3), (2, 4), (6, 7), (8, 12)], 0, 10)
    assert u == [(1, 4), (6, 7), (8, 10)]
    assert tm.length(u) == 6
    assert tm.subtract([(0, 10)], u) == [(0, 1), (4, 6), (7, 8)]


def test_synthetic_metrics():
    ctx = _ctx(SYNTH, 0, 10 * MS)
    # busy: [1,4] + [4.5,5] + [6,9] = 3 + 0.5 + 3 = 6.5 ms of 10
    busy = tm.length(tm.busy(SYNTH, 0, 0, 10 * MS))
    assert busy == pytest.approx(6.5 * MS)
    read = lambda m: harness._load_file("metrics", m).read(ctx)  # noqa: E731
    assert read("device_idle_share") == pytest.approx(35.0)
    assert read("executor_host_ms") == pytest.approx(5.0)
    assert read("stage_device_ms") == pytest.approx(3.5)
    assert read("adam_device_ms") == pytest.approx(3.0)
    # the architecture module's model FLOPs over the 10-ms window at 1e12
    assert read("mfu") == pytest.approx(
        100 * qwen2.model_flops_train(SIZES, 2, 128) / (10e-3 * 1e12))
    # one 0.5 ms kernel; 2 layers x 2 rows x 1 step calls
    f, b = flops.flash_attention_work(1, 128, 4, 4, 16)
    least = 4 * max(f / 1e12, b / 1e11)
    assert read("flash_roofline") == pytest.approx(100 * least / 0.5e-3)
    gaps = tm.idle_gaps(SYNTH, 0, 0, 10 * MS, ("batch", "executor.step",
                                               "adam.update", "loss_read"))
    # idle: [0,1] in batch, [4,4.5] and [5,6] in executor.step, [9,10]
    # begins in adam.update; longest first, ties in time order
    assert gaps == [["batch", pytest.approx(1e-3)],
                    ["executor.step", pytest.approx(1e-3)],
                    ["adam.update", pytest.approx(1e-3)],
                    ["executor.step", pytest.approx(0.5e-3)]]
    top = dict(tm.top_ops(SYNTH, [0], 0, 10 * MS))
    assert top["jit_adam_update:all-reduce"] == pytest.approx(2.5e-3)
    assert top["jit_fn:custom-call"] == pytest.approx(0.5e-3)


def test_metric_absent_without_its_events():
    empty = Trace(ops={0: []}, modules={0: []},
                  host=[Event(0, 10 * MS, "window")])
    ctx = _ctx(empty, 0, 10 * MS)
    for m in ("flash_roofline", "stage_device_ms", "adam_device_ms",
              "executor_host_ms"):
        assert harness._load_file("metrics", m).read(ctx) is None


def _naive_busy(events, lo, hi):
    """Busy time by a sweep over sorted end points (another algorithm than
    ``trace.union``)."""
    pts = sorted([(max(e.start, lo), 1) for e in events if e.end > lo and e.start < hi]
                 + [(min(e.end, hi), -1) for e in events if e.end > lo and e.start < hi])
    depth, last, total = 0, None, 0.0
    for t, d in pts:
        if depth > 0:
            total += t - last
        depth += d
        last = t
    return total


def test_recorded_tiny_trace():
    tr = tm.load(str(DATA.parent), harness.HOST_SPANS)
    (win,) = tr.spans("window")
    lo, hi = win.start, win.end
    assert [e.name for e in tr.host].count("executor.step") == 3
    ops = tr.ops[0]
    assert ops, "the recorded trace holds the chip's operations"
    busy = tm.length(tm.busy(tr, 0, lo, hi))
    assert busy == pytest.approx(_naive_busy(ops, lo, hi))
    assert 0 < busy < hi - lo
    ctx = _ctx(tr, lo, hi, steps=3)
    idle = harness._load_file("metrics", "device_idle_share").read(ctx)
    assert idle == pytest.approx(100 * (1 - busy / (hi - lo)))
    mods = {tm.module_name(e.name) for e in tr.modules[0]}
    assert {"jit_fn", "jit_adam_update"} <= mods
    kernels = [e for e in ops if tm.opcode(e.name) == "custom-call"]
    assert len(kernels) == 3
    assert harness._load_file("metrics", "adam_device_ms").read(ctx) > 0
    labels = {g[0] for g in tm.idle_gaps(tr, 0, lo, hi, harness.HOST_SPANS[1:])}
    assert "batch" in labels
