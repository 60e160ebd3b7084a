"""The unified event stream (repro.obs): schema, zero-cost contract,
exporter round trip, metrics folds, and sim-vs-real audits.

Three repo invariants live here (docs/observability.md):

  * zero cost when no observer is attached — the simulator's golden
    makespans/timelines and the executor's events=None default are
    bit-identical to the pre-instrumentation engine,
  * one lossless trace format — every span field (WAIT ``+w`` halves,
    sequence slices ``.sN``, channel keys, HBM samples) survives the
    Perfetto round trip,
  * one instruction census — the simulator and the real executor event
    streams of the SAME ScheduleSpec contain the same instruction set.
"""
import json

import pytest
from hypothesis import given, settings, strategies as st

import repro.core.plan as P
import repro.core.simulator as SIM
from repro.core.schedule import B, EVICT, F, LOAD
from repro.obs import CHANNEL, COMPUTE, ISSUE, WAIT, Recorder, Timeline
from repro.obs import compare as OC
from repro.obs import events as OE
from repro.obs import export as OX
from repro.obs import metrics as OM
from repro.planner import calibrate


def _sim_cfg(spec, **kw):
    kw.setdefault("Tf", 1.0)
    kw.setdefault("Tb", 2.0)
    kw.setdefault("t_p2p", 0.125)
    return SIM.SimConfig(spec=spec, **kw)


def _record(cfg):
    rec = Recorder()
    res = SIM.simulate(cfg, observer=rec)
    return rec, res


# ---------------------------------------------------------------------------
# Schema
# ---------------------------------------------------------------------------
def test_phase_constants_match_plan_ir():
    assert (OE.ISSUE, OE.WAIT) == (P.ISSUE, P.WAIT)


def test_span_key_matches_planned_instr_identity():
    spec = P.ScheduleSpec("bpipe", 4, 8, cap=2)
    sch = P.compile_plan(spec)
    rec, _ = _record(_sim_cfg(spec, evict_bytes=1.0, pair_bw=2.0))
    instr_keys = {(x.op, i, x.mb, x.chunk, x.sl, x.phase)
                  for i, stream in sch.streams.items() for x in stream}
    assert rec.keys() == instr_keys
    # exactly one compute span per compiled instruction — census, not
    # just coverage
    assert len(rec.compute_spans()) == sch.size


def test_span_label_spells_legacy_suffixes():
    s = OE.make(EVICT, 3, 3, chunk=1, sl=2, phase=WAIT)
    assert s.label == "EVICT3.c1.s2+w"
    assert not s.canonical and s.is_wait
    assert OE.make(F, 0, 1).canonical


# ---------------------------------------------------------------------------
# Zero-cost contract
# ---------------------------------------------------------------------------
def test_sim_observer_is_zero_cost_on_golden_cases():
    cases = [c for c in json.load(open("tests/golden/plan_golden.json"))
             if "residency" not in c]
    assert cases
    for c in cases[::3]:   # every 3rd case keeps this under a second
        spec = P.ScheduleSpec(c["kind"], c["p"], c["m"],
                              v=max(c["v"], 1), cap=c["cap"],
                              seq_chunks=c.get("seq_chunks", 1))
        cfg = _sim_cfg(spec, evict_bytes=1.0, pair_bw=2.0, pair_hops=1)
        base = SIM.simulate(cfg)
        rec, res = _record(cfg)
        assert res.makespan == base.makespan == c["makespan"]
        assert res.timeline == base.timeline
        assert rec.makespan == res.makespan


def test_dispatch_order_is_engine_order():
    spec = P.ScheduleSpec("1f1b", 2, 4)
    sch = P.compile_plan(spec)
    rec, _ = _record(_sim_cfg(spec))
    assert len(rec.dispatches) == sum(len(s) for s in sch.streams.values())
    # per stage, dispatch order IS stream order (streams are consumed
    # strictly in order)
    for i, stream in sch.streams.items():
        got = [d.key for d in rec.dispatches if d.stage == i]
        want = [(x.op, i, x.mb, x.chunk, x.sl, x.phase) for x in stream]
        assert got == want


# ---------------------------------------------------------------------------
# Exporter round trip
# ---------------------------------------------------------------------------
span_strategy = st.tuples(
    st.integers(0, 4),            # op index
    st.integers(0, 5),            # stage
    st.integers(0, 7),            # mb
    st.integers(0, 2),            # chunk
    st.integers(0, 3),            # sl
    st.integers(0, 2),            # phase index
    st.floats(0.0, 100.0),        # start
    st.floats(0.0, 10.0),         # duration
    st.integers(0, 3),            # track/channel selector
)
_OPS = (F, B, EVICT, LOAD, "OFFLOAD")
_PHASES = ("", ISSUE, WAIT)
_CHANNELS = (None, ("peer", 0, 3), ("d2h", 1), ("h2d", 2))


def _mk_span(t):
    op, stage, mb, chunk, sl, ph, start, dur, chan = t
    channel = _CHANNELS[chan]
    return OE.make(_OPS[op], stage, mb, chunk, sl, _PHASES[ph],
                   start=start, end=start + dur,
                   track=CHANNEL if channel else COMPUTE,
                   channel=channel,
                   hbm=float(mb * 100) if channel is None else None)


@settings(max_examples=40)
@given(st.lists(span_strategy, min_size=1, max_size=30))
def test_export_round_trip_is_lossless(tuples):
    import os
    import tempfile
    spans = [_mk_span(t) for t in tuples]
    fd, path = tempfile.mkstemp(suffix=".trace.json")
    os.close(fd)
    try:
        OX.save_trace(spans, path)
        back = OX.load_trace(path)
    finally:
        os.unlink(path)
    assert len(back) == len(spans)
    # multiset equality over every structured field + times
    def norm(ss):
        return sorted((s.key, round(s.start, 6), round(s.duration, 6),
                       s.track, s.channel, s.hbm) for s in ss)
    assert norm(back) == norm(spans)


def test_round_trip_keeps_wait_and_slice_fields(tmp_path):
    """Regression for the ad-hoc serializer this exporter replaced: a
    sliced, depth-2 simulated trace must reload with its WAIT halves and
    slice indices intact (it used to fold them into op strings and lose
    them, mis-binning move medians on re-fit)."""
    spec = P.ScheduleSpec("bpipe", 6, 6, cap=4, seq_chunks=2, depth=2)
    rec, _ = _record(_sim_cfg(spec, evict_bytes=1.0, pair_bw=2.0))
    assert any(s.sl > 0 for s in rec.spans)
    assert any(s.is_wait for s in rec.spans)
    assert any(s.track == CHANNEL for s in rec.spans)
    path = str(tmp_path / "sliced.trace.json")
    OX.save_trace(rec.spans, path)
    back = OX.load_trace(path)
    assert {s.key for s in back} == {s.key for s in rec.spans}
    assert (sum(1 for s in back if s.is_wait)
            == sum(1 for s in rec.spans if s.is_wait))
    assert (sum(1 for s in back if s.track == CHANNEL)
            == sum(1 for s in rec.spans if s.track == CHANNEL))
    f1 = calibrate.fit_trace(rec.spans, v=1, seq_chunks=2)
    f2 = calibrate.fit_trace(back, v=1, seq_chunks=2)
    assert (f1.Tf, f1.Tb, f1.t_evict, f1.t_load) == pytest.approx(
        (f2.Tf, f2.Tb, f2.t_evict, f2.t_load))


def test_chrome_events_carry_structured_args_and_counters():
    spans = [OE.make(F, 0, 0, start=0.0, end=1.0, hbm=64.0),
             OE.make(EVICT, 0, 1, phase=ISSUE, start=1.0, end=1.25),
             OE.make(EVICT, 0, 1, phase="", start=1.0, end=2.0,
                     track=CHANNEL, channel=("peer", 0, 3))]
    doc = OX.to_chrome(spans, counters={0: [(0.0, 0.0), (1.0, 64.0)]})
    events = doc["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert all("op" in e["args"] for e in xs)
    chan = [e for e in xs if e["args"]["track"] == CHANNEL]
    assert chan and chan[0]["pid"] != xs[0]["pid"]
    assert any(e["ph"] == "C" for e in events)


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------
def test_metrics_agree_with_simulator_accounting():
    spec = P.ScheduleSpec("bpipe", 4, 8, cap=2)
    cfg = _sim_cfg(spec, evict_bytes=1.0, pair_bw=2.0)
    rec, res = _record(cfg)
    met = OM.compute(rec.spans, p=spec.p, channel_stats=res.channels)
    assert met.makespan == res.makespan
    assert met.bubble_fraction == pytest.approx(res.bubble_fraction)
    for i, s in enumerate(met.stages):
        assert s.busy == pytest.approx(res.busy[i])
    assert {c.key for c in met.channels} == set(res.channels)
    for c in met.channels:
        st_ = res.channels[c.key]
        assert c.moves == st_.moves
        assert c.busy == pytest.approx(st_.busy)
        assert c.stall == pytest.approx(st_.stall)
        assert c.queue_peak == st_.queue_peak
    assert 0.0 < met.channel_occupancy() <= 1.0


def test_warmup_steady_drain_partition_the_step():
    spec = P.ScheduleSpec("1f1b", 4, 8)
    rec, res = _record(_sim_cfg(spec))
    met = OM.compute(rec.spans, p=spec.p)
    for s in met.stages:
        assert s.warmup >= 0 and s.steady >= 0 and s.drain >= 0
        assert s.warmup + s.steady + s.drain <= res.makespan + 1e-9
        assert 0.0 <= s.bubble_fraction < 1.0


def test_hbm_timeline_repriced_matches_stash_peaks():
    spec = P.ScheduleSpec("bpipe", 4, 8, cap=2)
    sch = P.compile_plan(spec)
    rec, _ = _record(_sim_cfg(spec, evict_bytes=1.0, pair_bw=2.0))
    series = OM.hbm_timeline(rec.spans, sch.partner, unit_bytes=1.0,
                             p=spec.p)
    peaks = OM.hbm_peaks(series)
    # unit weights = stash units: each stage's re-priced peak is at
    # least the plan's peak stash — the evictor stages (0, 1) ride one
    # unit above their cap while an eviction is in flight (the release
    # lands at the EVICT span's end, after the next F has stashed),
    # which is exactly the transient a byte *timeline* should show and
    # instantaneous stash accounting cannot
    assert all(peaks[i] >= float(sch.peak_stash[i])
               for i in range(spec.p))
    assert peaks == {0: 3.0, 1: 3.0, 2: 4.0, 3: 4.0}


def test_metrics_mfu_line():
    spec = P.ScheduleSpec("1f1b", 2, 4)
    rec, res = _record(_sim_cfg(spec))
    met = OM.compute(rec.spans, p=2, model_flops=12.0, t=1, peak_flops=1.0)
    assert met.mfu == pytest.approx(
        SIM.mfu_from_sim(res, 12.0, 2, 1, 1.0))


def test_fit_trace_bins_waits_and_skips_channel_spans():
    spans = [OE.make(F, 0, 0, start=0.0, end=1.0),
             OE.make(B, 0, 0, start=1.0, end=3.0),
             OE.make(LOAD, 0, 1, phase=ISSUE, start=3.0, end=3.5),
             OE.make(LOAD, 0, 1, phase=WAIT, start=3.5, end=4.5),
             OE.make(LOAD, 0, 1, start=3.0, end=3.5,
                     track=CHANNEL, channel=("peer", 0, 1))]
    fit = calibrate.fit_trace(spans)
    assert (fit.Tf, fit.Tb) == (1.0, 2.0)
    assert fit.t_load == 0.5       # the ISSUE half, not the WAIT barrier
    assert fit.samples == 5        # but the census counts everything


# ---------------------------------------------------------------------------
# Compare: sim-vs-real alignment
# ---------------------------------------------------------------------------
def test_compare_scaled_self_has_unit_skew_and_zero_divergence():
    spec = P.ScheduleSpec("bpipe", 4, 8, cap=2)
    rec, _ = _record(_sim_cfg(spec, evict_bytes=1.0, pair_bw=2.0))
    scaled = [OE.make(s.op, s.stage, s.mb, s.chunk, s.sl, s.phase,
                      start=2.0 * s.start, end=2.0 * s.end,
                      track=s.track, channel=s.channel)
              for s in rec.spans]
    rep = OC.compare(rec.spans, scaled, label="self*2")
    assert rep.instruction_sets_match
    assert rep.time_scale == pytest.approx(2.0)
    assert rep.max_order_divergence == 0.0
    assert all(s.skew == pytest.approx(1.0) for s in rep.op_skew)
    assert "self*2" in rep.format()
    assert json.dumps(rep.to_dict())


def test_compare_flags_census_and_order_divergence():
    spec = P.ScheduleSpec("1f1b", 2, 4)
    rec, _ = _record(_sim_cfg(spec))
    spans = rec.compute_spans()
    # drop one instruction and swap two starts on stage 0
    broken = [s for s in spans if not (s.op == B and s.mb == 3
                                       and s.stage == 1)]
    f0 = [s for s in broken if s.stage == 0 and s.op == F][:2]
    swapped = []
    for s in broken:
        if s is f0[0]:
            swapped.append(OE.make(s.op, s.stage, s.mb, start=f0[1].start,
                                   end=f0[1].start + s.duration))
        elif s is f0[1]:
            swapped.append(OE.make(s.op, s.stage, s.mb, start=f0[0].start,
                                   end=f0[0].start + s.duration))
        else:
            swapped.append(s)
    rep = OC.compare(spans, swapped)
    assert not rep.instruction_sets_match
    assert [k[0] for k in rep.missing_in_real] == [B]
    assert rep.order_div[0] > 0.0


def test_order_divergence_bounds():
    assert OC.order_divergence([1, 2, 3], [1, 2, 3]) == 0.0
    assert OC.order_divergence([1, 2, 3], [3, 2, 1]) == 1.0
    assert OC.order_divergence([], []) == 0.0


# ---------------------------------------------------------------------------
# The executor side (real jax numerics)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def exec_setup():
    import dataclasses

    import jax

    from repro.configs import get_config
    from repro.models import model as M
    cfg = dataclasses.replace(get_config("qwen1.5-0.5b").reduced(),
                              num_layers=8, dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(7), (8, 17), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    return cfg, params, batch


AUDIT_SPECS = [
    P.ScheduleSpec("bpipe", 4, 8, cap=2),
    P.ScheduleSpec("1f1b", 4, 8, residency="host_offload", depth=2),
    P.ScheduleSpec("1f1b", 4, 8, residency="selective_recompute"),
    P.ScheduleSpec("bpipe", 4, 8, cap=2, seq_chunks=2),
]


@pytest.mark.parametrize("spec", AUDIT_SPECS, ids=lambda s: s.label())
def test_sim_and_executor_streams_share_one_instruction_set(
        exec_setup, spec):
    """The differential census invariant: for the same spec, the
    simulated and the real event streams contain the same instruction
    set — every key the model prices is executed, and vice versa."""
    from repro.pipeline.executor import PipelineExecutor
    cfg, params, batch = exec_setup
    ex = PipelineExecutor(cfg, spec=spec, micro_batch=1)
    res = ex.step(params, batch, trace=True)
    costs = calibrate.fit_trace(res.events, v=spec.v, b=1,
                                seq_chunks=spec.seq_chunks)
    rec, _ = _record(SIM.SimConfig(spec=spec, Tf=costs.Tf, Tb=costs.Tb,
                                   evict_bytes=1.0, pair_bw=2.0,
                                   d2h_bw=2.0, h2d_bw=2.0))
    rep = OC.compare(rec.spans, res.events, label=spec.label())
    assert rep.instruction_sets_match, rep.format()
    assert rep.sim_count == rep.real_count
    assert rep.time_scale > 0


def test_executor_trace_records_hbm_samples_and_timeline(exec_setup):
    from repro.pipeline.executor import PipelineExecutor
    cfg, params, batch = exec_setup
    spec = P.ScheduleSpec("bpipe", 4, 8, cap=2)
    ex = PipelineExecutor(cfg, spec=spec, micro_batch=1)
    assert ex.step(params, batch).events is None   # zero-observer default
    res = ex.step(params, batch, trace=True)
    hbm = [s for s in res.events if s.hbm is not None]
    assert hbm and max(s.hbm for s in hbm) > 0
    series = OM.hbm_timeline(res.events, P.compile_plan(spec).partner,
                             unit_bytes=0.0, p=spec.p)
    assert max(v for ser in series.values() for _, v in ser) > 0


def test_custom_observer_streams_executor_spans(exec_setup):
    """observer= without trace=True: spans stream to the caller's
    observer and the step result carries no event list."""
    from repro.pipeline.executor import PipelineExecutor

    class Counting(OE.Observer):
        def __init__(self):
            self.n = 0
            self.dispatched = 0

        def span(self, span):
            self.n += 1

        def dispatch(self, stage, ins):
            self.dispatched += 1

    cfg, params, batch = exec_setup
    obs = Counting()
    spec = P.ScheduleSpec("1f1b", 4, 8)
    ex = PipelineExecutor(cfg, spec=spec, micro_batch=1)
    res = ex.step(params, batch, observer=obs)
    assert res.events is None
    sch = P.compile_plan(spec.with_m(8))
    total = sum(len(s) for s in sch.streams.values())
    assert obs.dispatched == total
    assert obs.n == total          # compute spans; 1f1b moves nothing
