"""The executor's profiler spans (``pipeline.*``, docs/observability.md
"On the chip"): two tiny BPipe steps profiled on the CPU, read back with
the benchmark's trace reader (``bench/trace.py``) and its host-time
reducers (``bench/metrics``).

The CPU backend writes ``TraceAnnotation`` spans to the host plane
``/host:CPU``, line ``python``, as the chip's runtime does.
"""
import collections
import dataclasses
import glob
import os
import sys

import jax
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness, trace as tm  # noqa: E402
from bench.host_split import SPLIT  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import plan as P  # noqa: E402
from repro.core.schedule import B, F  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.pipeline.executor import PipelineExecutor  # noqa: E402

SPEC = P.ScheduleSpec("bpipe", 4, 8)
INTERP = harness._load_file("metrics", "interp_host_ms")
MOVES, PIPELINE = INTERP.MOVES, INTERP.PIPELINE


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """Steps 1 and 2 of a tiny ``bpipe p=4 m=8`` executor (step 0
    compiles), each inside an ``executor.step`` span as the benchmark
    places it: the trace, its raw profile and the compiled schedule."""
    cfg = dataclasses.replace(get_config("qwen1.5-0.5b").reduced(),
                              num_layers=4, dtype="float32")
    ex = PipelineExecutor(cfg, spec=SPEC, micro_batch=1)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    jax.block_until_ready(ex.step(params, batch).grads)
    d = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("executor.step"):
                r = ex.step(params, batch)
        jax.block_until_ready(r.grads)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    tr = tm.from_profile(pd, harness.HOST_SPANS + PIPELINE)
    args = [dict(e.stats) for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith("pipeline.")]
    return tr, args, P.compile_plan(SPEC)


def _instructions(schedule):
    return [(i, ins) for i, stream in schedule.streams.items()
            for ins in stream]


def test_instruction_spans_match_the_compiled_schedule(profiled):
    tr, _, schedule = profiled
    want = collections.Counter((ins.op, ins.phase)
                               for _, ins in _instructions(schedule))
    assert want[(F, "")] == want[(B, "")] == 4 * 8
    assert want[("EVICT", "issue")] == want[("EVICT", "wait")] > 0
    assert want[("LOAD", "issue")] == want[("LOAD", "wait")] > 0
    for op in (F, B, *MOVES):
        n = sum(k for (o, _), k in want.items() if o == op)
        assert len(tr.spans(f"pipeline.{op}")) == 2 * n, op


def test_instruction_spans_carry_their_identity(profiled):
    _, args, schedule = profiled
    want = sorted((ins.op, i, ins.mb, ins.chunk, ins.sl, ins.phase)
                  for i, ins in _instructions(schedule))
    by_step = collections.defaultdict(list)
    for a in args:
        if "op" in a:
            by_step[a["step"]].append((a["op"], a["stage"], a["mb"],
                                       a["chunk"], a["sl"],
                                       a.get("phase", "")))
    assert sorted(by_step) == [1, 2]
    for got in by_step.values():
        assert sorted(got) == want


def test_grad_accum_nests_inside_b(profiled):
    tr, args, _ = profiled
    bs = tr.spans("pipeline.B")
    accum = tr.spans("pipeline.grad_accum")
    assert len(accum) == len(bs) == 2 * 4 * 8
    for a in accum:
        assert any(b.start <= a.start and a.end <= b.end for b in bs)
    stages = collections.Counter(a["stage"] for a in args
                                 if "op" not in a and "stage" in a)
    assert stages == {i: 2 * 8 for i in range(4)}


def test_split_and_merge_once_per_step_inside_it(profiled):
    tr, args, _ = profiled
    steps = tr.spans("executor.step")
    assert len(steps) == 2
    for name in PIPELINE:
        for e in tr.spans(name):
            assert any(s.start <= e.start and e.end <= s.end for s in steps)
    for name in ("pipeline.split", "pipeline.merge"):
        assert len(tr.spans(name)) == 2
    assert sorted(a["step"] for a in args if set(a) == {"step"}) == [1, 1, 2, 2]


def test_host_split_adds_up_to_the_step(profiled):
    """F, B's self time, accumulation, split/merge, the interpreter and
    the moves partition the ``executor.step`` spans."""
    tr, _, _ = profiled
    (win,) = tr.spans("window")
    ctx = {"trace": tr, "lo": win.start, "hi": win.end, "devices": [0],
           "steps": 2, "trace_mod": tm}
    split = {m: harness._load_file("metrics", m).read(ctx) for m in SPLIT}
    assert all(v > 0 for v in split.values()), split
    moves = sum(e.end - e.start for op in MOVES
                for e in tr.spans(f"pipeline.{op}")) / 1e6 / 2
    total = harness._load_file("metrics", "executor_host_ms").read(ctx)
    assert sum(split.values()) + moves == pytest.approx(total, rel=1e-9)
