"""The schedule auto-planner: feasibility of everything it emits,
optimality against a brute-force simulator sweep, the paper's Table 3
win/loss verdicts from first principles, and the executor-trace
calibration round trip."""
import dataclasses

import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core import estimator as E
from repro.core import memory_model as MM
from repro.core import schedule as S
from repro.core import simulator as SIM
from repro.core.notation import A100_HBM_BYTES, GPT3_96B, LLAMA_65B, Notation
from repro.obs import export as OX
from repro.planner import (AnalyticCostModel, SearchSpace, Table5CostModel,
                           calibrate, plan_config, recommend, report)
from repro.planner import rank as R
from repro.planner import space as SP


def _n(p, B, b=1):
    return Notation(a=4, b=b, h=256, l=16, s=128, v=512, B=B, p=p, t=1)


def _small_ranked(p, B):
    n = _n(p, B)
    cost = AnalyticCostModel()
    # budget: the b=1 1F1B peak with a little headroom, so larger micro
    # batches (and fatter interleaved stashes) genuinely prune
    hbm = 1.2 * MM.max_stage_bytes(n, "recompute", "1f1b")
    cands = SP.enumerate_candidates(n, SearchSpace(vs=(2,)))
    return n, hbm, cost, R.rank(n, cands, cost, hbm, workspace=0.0)


# ---------------------------------------------------------------------------
# Property: everything the planner calls feasible IS feasible
# ---------------------------------------------------------------------------
@given(st.integers(2, 4), st.sampled_from([8, 16]))
@settings(max_examples=8, deadline=None)
def test_planner_emits_only_feasible_plans(p, B):
    n, hbm, _, ranked = _small_ranked(p, B)
    assert ranked, "search space empty"
    assert recommend(ranked) is not None
    for rp in ranked:
        c = rp.cand
        if rp.verdict == "pruned":
            # branch-and-bound discard: never simulated, no claims made
            assert rp.makespan == 0.0 and rp.mfu == 0.0
            continue
        if not rp.feas.ok:
            assert rp.verdict == "infeasible"
            continue
        # structural validity
        assert B % c.b == 0 and c.m == B // c.b
        if c.kind in S.INTERLEAVED:
            assert c.v >= 2 and c.m % p == 0
        # and the memory model agrees, cap-, v-chunk- and residency-aware
        peak = MM.max_stage_bytes(n.replace(b=c.b), c.attention, c.spec(p))
        assert peak <= hbm, (c, peak, hbm)
        assert peak == pytest.approx(rp.feas.peak_bytes)


# ---------------------------------------------------------------------------
# Property: the ranked-best plan never loses to a brute-force sweep
# ---------------------------------------------------------------------------
@given(st.integers(2, 4), st.sampled_from([8, 16]))
@settings(max_examples=6, deadline=None)
def test_best_plan_beats_bruteforce_sim_sweep(p, B):
    n, hbm, cost, ranked = _small_ranked(p, B)
    survivors = [rp for rp in ranked if rp.ok]
    best = recommend(ranked)
    assert best is rp_max_mfu(survivors)
    for rp in survivors:
        c = rp.cand
        # brute force: re-simulate every survivor independently
        nb = n.replace(b=c.b)
        T = cost.stage_T(nb, c.attention)
        spec = c.spec(p)
        res = SIM.simulate(SIM.SimConfig(
            spec=spec, Tf=T / 3.0, Tb=2.0 * T / 3.0,
            evict_bytes=(MM.eviction_bytes(nb, c.attention, c.v)
                         if spec.policy.moves_data else 0.0),
            pair_bw=R.NVLINK_BW, pair_hops=max(rp.feas.pair_hops, 1),
            d2h_bw=R.PCIE_BW, h2d_bw=R.PCIE_BW))
        assert rp.makespan == pytest.approx(res.makespan)
        assert best.makespan <= res.makespan + 1e-12, (best.cand, c)


def rp_max_mfu(survivors):
    return max(survivors, key=lambda rp: rp.mfu, default=None)


# ---------------------------------------------------------------------------
# Paper Table 3 verdicts, reproduced from first principles
# ---------------------------------------------------------------------------
def test_gpt3_verdict_bpipe_wins_under_recompute():
    ranked = plan_config(GPT3_96B, get_config("gpt3-96b"), A100_HBM_BYTES)
    rec = recommend(ranked, "recompute")
    assert rec is not None
    assert rec.cand.kind in S.BPIPE_FAMILY and rec.cand.b == 2
    # the win is memory-made: UNMANAGED 1F1B cannot hold b=2 on an
    # A100-80G (residency-managed 1f1b variants can — that is the point)
    oom = [rp for rp in ranked
           if rp.cand.kind == "1f1b" and rp.cand.b == 2
           and rp.cand.attention == "recompute"
           and rp.cand.residency == "none"]
    assert oom and all(rp.verdict == "infeasible" for rp in oom)
    # flash arm: the paper's BPipe row loses — planner must not pick BPipe
    rec_flash = recommend(ranked, "flash")
    assert rec_flash.cand.kind not in S.BPIPE_FAMILY


def test_llama_verdict_bpipe_rejected_at_break_even():
    ranked = plan_config(LLAMA_65B, get_config("llama-65b"), A100_HBM_BYTES)
    for arm in ("recompute", "flash", None):
        rec = recommend(ranked, arm)
        assert rec is not None
        assert rec.cand.kind not in ("bpipe",), (arm, rec.cand)
    # larger-b plans are feasible but fail the paper's break-even bar:
    # required (B + 4(p-1)) / (B + 2(p-1)) = 156/142, measured Table 5
    # stage gain 57.6/54.5
    rej = [rp for rp in ranked
           if rp.cand.kind == "bpipe" and rp.cand.b == 4
           and rp.cand.attention == "recompute" and rp.cand.cap is None
           and rp.cand.depth == 1]
    assert len(rej) == 1 and rej[0].verdict == "reject"
    assert rej[0].required_gain == pytest.approx(156.0 / 142.0)
    assert rej[0].achieved_gain == pytest.approx(57.6 / 54.5, rel=1e-3)
    # the overall recommendation is a non-BPipe-family plan (Table 3:
    # every LLaMA BPipe row is a regression)
    overall = recommend(ranked)
    assert overall.cand.kind not in S.BPIPE_FAMILY


def test_rejections_cite_required_gain_in_table_and_summary():
    ranked = plan_config(LLAMA_65B, get_config("llama-65b"), A100_HBM_BYTES)
    table = report.format_table(ranked)
    assert "reject" in table and "1.099" in table
    line = report.recommendation_line("llama-65b", ranked, "recompute")
    assert "required 1.099x" in line and "1.057x" in line


def test_planner_cli_end_to_end(capsys):
    import json as _json
    from repro.core import plan as P
    from repro.launch import plan as plan_cli
    plan_cli.main(["--config", "gpt3_96b", "--attention", "recompute",
                   "--top", "3", "--spec-json"])
    out = capsys.readouterr().out
    assert "PLAN gpt3-96b [recompute]: bpipe b=2" in out
    assert "req_gain" in out
    # --spec-json round-trips the FULL spec, residency included
    specs = [_json.loads(ln) for ln in out.splitlines()
             if ln.startswith("{")]
    assert specs
    for rec in specs:
        spec = P.ScheduleSpec.from_dict(rec["spec"])
        assert set(rec["spec"]) == set(P.ScheduleSpec.DICT_KEYS)
        assert spec.to_dict() == rec["spec"]
        assert spec.residency == "bpipe_swap"       # the winning plan's
    plan_cli.main(["--config", "llama_65b", "--csv"])
    out = capsys.readouterr().out
    assert "verdict=reject" in out and ",res=" in out


# ---------------------------------------------------------------------------
# Cap as a search dimension
# ---------------------------------------------------------------------------
def test_looser_cap_trades_evictions_for_memory():
    p, m = 8, 32
    prev_ev = None
    for cap in range(S.bpipe_cap(p), p + 1):
        streams = S.build("bpipe", p, m, cap=cap)
        ev = sum(1 for s in streams.values() for i in s if i.op == S.EVICT)
        peaks = S.peak_stash("bpipe", p, m, cap=cap)
        assert max(peaks[i] for i in range(p // 2)) <= cap + 1
        if prev_ev is not None:
            assert ev <= prev_ev, (cap, ev, prev_ev)
        prev_ev = ev
    assert ev == 0  # cap == 1F1B peak: degenerates to no balancing


def test_executor_honors_custom_cap():
    cfg = dataclasses.replace(get_config("qwen1.5-0.5b").reduced(),
                              num_layers=4, dtype="float32")
    import jax
    from repro.models import model as M
    from repro.pipeline.executor import PipelineExecutor
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    default = PipelineExecutor(cfg, p=4, kind="bpipe", micro_batch=1)
    loose = PipelineExecutor(cfg, p=4, kind="bpipe", micro_batch=1,
                             cap=S.bpipe_cap(4) + 1)
    r0, r1 = default.step(params, batch), loose.step(params, batch)
    assert abs(float(r0.loss - r1.loss)) < 1e-6
    assert r1.stats.evictions < r0.stats.evictions
    assert max(r1.stats.peak_local[i] for i in (0, 1)) <= S.bpipe_cap(4) + 1


# ---------------------------------------------------------------------------
# Trace -> calibrate round trip (the §4 recipe, programmatically)
# ---------------------------------------------------------------------------
def _traced_step(kind="bpipe", p=4, layers=4, rows=8):
    import jax
    from repro.models import model as M
    from repro.pipeline.executor import PipelineExecutor
    cfg = dataclasses.replace(get_config("qwen1.5-0.5b").reduced(),
                              num_layers=layers, dtype="float32")
    ex = PipelineExecutor(cfg, p=p, kind=kind, micro_batch=1)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (rows, 17), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    ex.step(params, batch)                  # compile step, not traced
    return ex, cfg, ex.step(params, batch, trace=True)


def test_trace_calibration_changes_simulator_costs(tmp_path):
    ex, cfg, res = _traced_step()
    events = res.events
    assert events is not None
    m = 8
    n_fb = sum(1 for e in events if e.op in (S.F, S.B))
    assert n_fb == 2 * 4 * m
    assert sum(1 for e in events
               if e.op == S.EVICT and e.canonical) == res.stats.evictions
    assert all(e.end >= e.start >= 0.0 for e in events)

    fit = calibrate.fit_trace(events, v=1, b=1)
    assert fit.Tf > 0 and fit.Tb > 0 and fit.samples == len(events)

    base = SIM.SimConfig(p=4, m=m, Tf=1.0, Tb=2.0, kind="bpipe")
    cal = calibrate.apply(fit, base)
    assert (cal.Tf, cal.Tb) == (fit.Tf, fit.Tb) != (1.0, 2.0)
    # the calibrated costs really drive the simulator
    assert SIM.simulate(cal).makespan != SIM.simulate(base).makespan
    assert SIM.simulate(cal).makespan == pytest.approx(
        calibrate.replay(fit, "bpipe", 4, m).makespan)

    # chrome-trace export round-trips losslessly enough to refit
    path = tmp_path / "step.trace.json"
    OX.save_trace(events, str(path))
    fit2 = calibrate.fit_trace(OX.load_trace(str(path)), v=1, b=1)
    assert fit2.Tf == pytest.approx(fit.Tf, rel=1e-6)
    assert fit2.Tb == pytest.approx(fit.Tb, rel=1e-6)


def test_untraced_step_has_no_events():
    ex, cfg, res = _traced_step(kind="1f1b", p=2, layers=2, rows=4)
    import jax
    from repro.models import model as M
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    assert ex.step(params, batch).events is None


def test_two_point_recipe_and_trace_cost_model():
    cfg = dataclasses.replace(get_config("qwen1.5-0.5b").reduced(),
                              num_layers=2, dtype="float32")
    out = calibrate.measure_stage_gain(cfg, bx=2, by=1, seq=16, m=2)
    assert out["Tx"] > 0 and out["Ty"] > 0 and out["gain"] > 0
    cm = calibrate.TraceCostModel(out["costs_x"])
    n = _n(p=2, B=8)
    assert cm.stage_T(n.replace(b=4), "none") > cm.stage_T(
        n.replace(b=2), "none")
    # saturating shape: larger b always helps per-sample throughput,
    # but with diminishing returns
    g = cm.stage_gain(n, 4, 2, "none")
    assert 1.0 < g < 1.2
    # the traced arm anchors; other arms scale by the analytic factors
    # (a none-mode trace must still charge recompute its re-forward)
    assert cm.stage_T(n, "recompute") > cm.stage_T(n, "none") \
        > cm.stage_T(n, "flash")


def test_interleaved_break_even_uses_interleaved_bubble():
    """A bpipe_interleaved plan whose simulated MFU beats the 1f1b
    baseline must not be rejected by the plain-bubble bar: its ramp is
    (p-1)/v, so the required gain shrinks accordingly (84 GiB admits the
    llama bpipe_interleaved v=4 b=4 plan the 80 GiB budget prunes)."""
    ranked = plan_config(LLAMA_65B, get_config("llama-65b"),
                         84 * 1024**3)
    il = [rp for rp in ranked
          if rp.cand.kind == "bpipe_interleaved" and rp.cand.b == 4
          and rp.cand.v == 4 and rp.cand.attention == "recompute"
          and rp.cand.cap is None and rp.cand.depth == 1]
    assert len(il) == 1 and il[0].verdict == "ok", il
    assert il[0].required_gain == pytest.approx(
        (128 + 4 * 7 / 4) / (128 + 2 * 7))
    # while the plain-bpipe b=4 plan is still rejected at the paper's bar
    plain = [rp for rp in ranked
             if rp.cand.kind == "bpipe" and rp.cand.b == 4
             and rp.cand.attention == "recompute" and rp.cand.cap is None]
    assert plain[0].verdict == "reject"
    assert plain[0].required_gain == pytest.approx(156.0 / 142.0)
