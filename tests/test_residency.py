"""The activation-residency subsystem (``repro.memory``): spec dimension
validation, the shared spill rewrite, registry-driven op-set extension
(dep edges + accounting + simulator pricing + engine round-robin /
deadlock paths), real executor numerics for host_offload /
selective_recompute, executor-vs-memory-model byte agreement, and the
planner's joint (kind, residency, cap) search."""
import dataclasses
import json

import pytest

from repro.core import memory_model as MM
from repro.core import plan as P
from repro.core import schedule as S
from repro.core import simulator as SIM
from repro.core.notation import Notation
from repro.core.schedule import (B, DROP, EVICT, F, FETCH, LOAD, OFFLOAD,
                                 RECOMPUTE)
from repro.memory import policy as respol
from repro.memory.store import ActivationStore

RESIDENCIES = ("host_offload", "selective_recompute")
OPS = {"host_offload": (OFFLOAD, FETCH),
       "selective_recompute": (DROP, RECOMPUTE)}


# ---------------------------------------------------------------------------
# ScheduleSpec: residency as a validated, normalized dimension
# ---------------------------------------------------------------------------
def test_residency_validation_and_normalization():
    with pytest.raises(ValueError, match="unknown residency"):
        P.ScheduleSpec("1f1b", 4, 8, residency="nvme_offload")
    # balanced kinds embed the swap: normalize, reject contradictions
    assert P.ScheduleSpec("bpipe", 4, 8).residency == "bpipe_swap"
    assert P.ScheduleSpec("bpipe", 4, 8, residency="bpipe_swap") \
        == P.ScheduleSpec("bpipe", 4, 8)
    with pytest.raises(ValueError, match="embeds the partner swap"):
        P.ScheduleSpec("bpipe", 4, 8, residency="host_offload")
    with pytest.raises(ValueError, match="built-in mechanism"):
        P.ScheduleSpec("1f1b", 4, 8, residency="bpipe_swap")
    # cap: active residency policies cap plain kinds; default collapses
    spec = P.ScheduleSpec("1f1b", 4, 8, residency="host_offload")
    assert spec.cap is None
    assert spec.resolved_cap == respol.residency_cap(4, 1) == S.bpipe_cap(4)
    assert P.ScheduleSpec("1f1b", 4, 8, residency="host_offload",
                          cap=S.bpipe_cap(4)) == spec
    with pytest.raises(ValueError, match="cap must be >= 2"):
        P.ScheduleSpec("1f1b", 4, 8, residency="selective_recompute", cap=1)
    # no residency management -> no cap
    assert P.ScheduleSpec("1f1b", 4, 8, cap=7).cap is None
    assert "res=host_offload" in spec.label()


def test_spec_dict_round_trip_rejects_unknown_keys():
    spec = P.ScheduleSpec("1f1b_interleaved", 4, 8, v=2,
                          residency="selective_recompute", cap=9)
    d = json.loads(json.dumps(spec.to_dict()))
    assert P.ScheduleSpec.from_dict(d) == spec
    # old dicts without the residency key still load (default "none")
    legacy = {"kind": "bpipe", "p": 4, "m": 8, "v": 1, "cap": None}
    assert P.ScheduleSpec.from_dict(legacy) == P.ScheduleSpec("bpipe", 4, 8)
    with pytest.raises(ValueError, match="unknown ScheduleSpec keys"):
        P.ScheduleSpec.from_dict({**d, "residencyy": "none"})
    with pytest.raises(ValueError, match="residencyy"):
        P.ScheduleSpec.from_dict({**d, "residencyy": "none"})


# ---------------------------------------------------------------------------
# One spill discipline: the new policies mirror bpipe's decisions exactly
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("residency", RESIDENCIES)
@pytest.mark.parametrize("kind,v", [("1f1b", 1), ("1f1b_interleaved", 2)])
def test_rewrite_mirrors_bpipe_spill_positions(kind, v, residency):
    """Same base schedule + same cap -> the release/restore ops land at
    exactly the positions bpipe's EVICT/LOAD land; only the op names
    (the mechanism) differ."""
    twin = {"1f1b": "bpipe", "1f1b_interleaved": "bpipe_interleaved"}[kind]
    p, m = 4, 8
    rel, res = OPS[residency]
    sub = {EVICT: rel, LOAD: res}
    bp = P.compile_plan(P.ScheduleSpec(twin, p, m, v=v)).instr_streams()
    got = P.compile_plan(
        P.ScheduleSpec(kind, p, m, v=v, residency=residency)).instr_streams()
    for i in range(p):
        want = [S.Instr(sub.get(x.op, x.op), x.mb, x.chunk) for x in bp[i]]
        assert got[i] == want


@pytest.mark.parametrize("residency", RESIDENCIES)
def test_compiled_accounting_peaks_and_spills(residency):
    spec = P.ScheduleSpec("1f1b", 4, 8, residency=residency)
    sch = P.compile_plan(spec)
    cap = spec.resolved_cap
    # the local stash honors the cap on every stage
    assert all(pk <= cap for pk in sch.peak_stash.values())
    # early stages spill (they hold the 1F1B imbalance), late ones don't
    assert sch.peak_spilled[0] > 0 and sch.peak_spilled[3] == 0
    # release waits on the unit's F; restore on its release
    rel, res = OPS[residency]
    r0 = next(x for x in sch.streams[0] if x.op == rel)
    assert r0.dep == (F, 0, r0.mb, r0.chunk, 0)
    s0 = next(x for x in sch.streams[0] if x.op == res)
    assert s0.dep == (rel, 0, s0.mb, s0.chunk, 0)
    # moves = release + restore count of the stream actually built
    assert P.num_moves(spec) == sum(sch.num_evictions.values()) \
        + sum(sch.num_loads.values()) > 0
    # partner map is the swap's business only
    assert sch.partner == {}


# ---------------------------------------------------------------------------
# Satellite: engine round-robin merge + deadlock paths over extended ops
# ---------------------------------------------------------------------------
def test_round_robin_accounting_drains_extended_ops():
    for residency in RESIDENCIES:
        spec = P.ScheduleSpec("1f1b", 4, 8, residency=residency)
        streams = P.compile_plan(spec).streams
        # greedy=False round-robin merge (what _account counts over)
        traces, spill_traces, counts = P._account(streams, 4)
        assert set(counts.values()) == {0}          # every stream drains
        assert max(spill_traces[0]) == \
            P.compile_plan(spec).peak_spilled[0]
        # legacy two-tuple view agrees
        t2, c2 = P.stash_accounting(streams, 4)
        assert t2 == traces and c2 == counts


def test_malformed_stream_deadlocks_with_message():
    # FETCH without a prior OFFLOAD: its dependency can never complete
    bad = {0: P._plan_stream(
        P.ScheduleSpec("1f1b", 1, 1, residency="host_offload"), 0,
        [S.Instr(F, 0), S.Instr(FETCH, 0), S.Instr(B, 0)])}
    with pytest.raises(P.ScheduleDeadlock, match="FETCH0@0"):
        P.run(bad, _sim_handlers(bad))
    # RECOMPUTE without DROP deadlocks the same way
    rec = {0: P._plan_stream(
        P.ScheduleSpec("1f1b", 1, 1, residency="selective_recompute"), 0,
        [S.Instr(F, 0), S.Instr(RECOMPUTE, 0), S.Instr(B, 0)])}
    with pytest.raises(P.ScheduleDeadlock) as e:
        P.run(rec, _sim_handlers(rec))
    assert "RECOMPUTE0@0" in str(e.value) and isinstance(
        e.value, RuntimeError)


def _sim_handlers(streams):
    """Minimal dataflow handlers: done-set semantics like the simulator."""
    done = set()

    def handler(i, ins):
        if ins.dep is not None and ins.dep not in done:
            return P.BLOCKED
        done.add(ins.done_key)
    return {op: handler for op in (F, B, EVICT, LOAD, OFFLOAD, FETCH,
                                   DROP, RECOMPUTE)}


# ---------------------------------------------------------------------------
# Simulator pricing by mechanism
# ---------------------------------------------------------------------------
def test_offload_priced_on_host_link():
    base = SIM.simulate(SIM.SimConfig(
        spec=P.ScheduleSpec("1f1b", 4, 8), Tf=1.0, Tb=2.0))
    spec = P.ScheduleSpec("1f1b", 4, 8, residency="host_offload")
    fast = SIM.simulate(SIM.SimConfig(spec=spec, Tf=1.0, Tb=2.0))
    # infinite host bandwidth: offload is free, makespan identical
    assert fast.makespan == base.makespan and fast.load_stall == 0.0
    slow = SIM.simulate(SIM.SimConfig(
        spec=spec, Tf=1.0, Tb=2.0, evict_bytes=4.0, d2h_bw=1.0, h2d_bw=1.0))
    assert slow.load_stall > 0.0 and slow.makespan > base.makespan
    assert slow.move_time > 0.0
    # the pair link is NOT involved: pair_bw cannot slow offload down
    pair_slow = SIM.simulate(SIM.SimConfig(
        spec=spec, Tf=1.0, Tb=2.0, evict_bytes=4.0, pair_bw=1e-9))
    assert pair_slow.makespan == base.makespan


def test_recompute_priced_as_compute():
    base = SIM.simulate(SIM.SimConfig(
        spec=P.ScheduleSpec("1f1b", 4, 8), Tf=1.0, Tb=2.0))
    spec = P.ScheduleSpec("1f1b", 4, 8, residency="selective_recompute")
    rec = SIM.simulate(SIM.SimConfig(spec=spec, Tf=1.0, Tb=2.0))
    n_rec = P.compile_plan(spec).num_loads[0]
    # stage 0 re-runs n_rec chunk forwards ON the compute frontier
    assert rec.busy[0] == pytest.approx(base.busy[0] + n_rec * 1.0)
    assert rec.makespan > base.makespan
    # bandwidth knobs cannot touch it: FLOPs, not bytes
    rec2 = SIM.simulate(SIM.SimConfig(
        spec=spec, Tf=1.0, Tb=2.0, evict_bytes=100.0,
        d2h_bw=1e-9, h2d_bw=1e-9, pair_bw=1e-9))
    assert rec2.makespan == rec.makespan


def test_legacy_simconfig_residency_knob():
    legacy = SIM.SimConfig(p=4, m=8, Tf=1.0, Tb=2.0, kind="1f1b",
                           residency="host_offload")
    spec = P.ScheduleSpec("1f1b", 4, 8, residency="host_offload")
    assert legacy.to_spec() == spec
    a = SIM.simulate(legacy)
    b = SIM.simulate(SIM.SimConfig(spec=spec, Tf=1.0, Tb=2.0))
    assert a.makespan == b.makespan and a.timeline == b.timeline
    # a cap override must survive the legacy-knob path: the policy is
    # what makes cap meaningful on a plain kind
    capped = SIM.SimConfig(p=4, m=8, Tf=1.0, Tb=2.0, kind="1f1b",
                           residency="host_offload", cap=4)
    assert capped.to_spec().resolved_cap == 4
    assert capped.to_spec() == P.ScheduleSpec("1f1b", 4, 8, cap=4,
                                              residency="host_offload")


# ---------------------------------------------------------------------------
# Memory model: per-policy byte accounting
# ---------------------------------------------------------------------------
def test_per_policy_byte_accounting():
    n = Notation(a=4, b=2, h=256, l=16, s=128, v=512, B=16, p=4, t=1)
    att = "recompute"
    per_mb = MM.act_bytes_per_stage(n, att, 1)
    plain = MM.per_stage_memory(n, att, "1f1b")
    off = MM.per_stage_memory(
        n, att, P.ScheduleSpec("1f1b", 4, n.num_micro,
                               residency="host_offload"))
    rec = MM.per_stage_memory(
        n, att, P.ScheduleSpec("1f1b", 4, n.num_micro,
                               residency="selective_recompute"))
    # stage 0 spills under the cap: offload frees the full unit to host,
    # recompute retains the boundary input — strict ordering
    assert off[0].act_bytes < rec[0].act_bytes < plain[0].act_bytes
    assert off[0].host_bytes > 0 and rec[0].host_bytes == 0.0
    sch = P.compile_plan(P.ScheduleSpec("1f1b", 4, n.num_micro,
                                        residency="host_offload"))
    assert off[0].host_bytes == pytest.approx(
        sch.peak_spilled[0] * per_mb)
    boundary = 2.0 * n.s * n.b * n.h / n.t
    assert rec[0].act_bytes == pytest.approx(
        sch.peak_stash[0] * per_mb + sch.peak_spilled[0] * boundary)
    # traffic: offload moves bytes, recompute does not
    assert MM.traffic_bytes(
        n, att, P.ScheduleSpec("1f1b", 4, n.num_micro,
                               residency="host_offload")) > 0.0
    assert MM.traffic_bytes(
        n, att, P.ScheduleSpec("1f1b", 4, n.num_micro,
                               residency="selective_recompute")) == 0.0


# ---------------------------------------------------------------------------
# Executor: real numerics for both policies, byte agreement regression
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def exec_setup():
    import jax
    from repro.configs import get_config
    from repro.models import model as M
    cfg = dataclasses.replace(get_config("qwen1.5-0.5b").reduced(),
                              num_layers=8, dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(7), (8, 9), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    ref_loss, _ = M.loss_fn(params, batch, cfg)
    return cfg, params, batch, ref_loss


@pytest.mark.parametrize("residency", RESIDENCIES)
def test_executor_residency_matches_loss_fn(exec_setup, residency):
    import jax
    import numpy as np
    from repro.pipeline import PipelineExecutor
    cfg, params, batch, ref_loss = exec_setup
    base = PipelineExecutor(cfg, spec=P.ScheduleSpec("1f1b", 4, 8),
                            micro_batch=1)
    r0 = base.step(params, batch)
    ex = PipelineExecutor(
        cfg, spec=P.ScheduleSpec("1f1b", 4, 8, residency=residency),
        micro_batch=1)
    r1 = ex.step(params, batch)
    # fp32 contract vs the non-pipelined reference...
    assert abs(float(r1.loss - ref_loss)) < 1e-5
    # ...and bit-identical to the resident execution: the offload round
    # trip moves arrays losslessly, the re-forward is deterministic
    assert float(r1.loss) == float(r0.loss)
    for a, b in zip(jax.tree.leaves(r0.grads), jax.tree.leaves(r1.grads)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    s = r1.stats
    if residency == "host_offload":
        assert s.offloads == s.fetches > 0 and s.drops == 0
        assert s.bytes_moved > 0 and max(s.host_peak_bytes.values()) > 0
    else:
        assert s.drops == s.recomputes > 0 and s.offloads == 0
        assert s.bytes_moved == 0.0
    # the residency cap really bounds the live store
    cap = P.ScheduleSpec("1f1b", 4, 8, residency=residency).resolved_cap
    assert max(s.peak_local.values()) <= cap


def test_offload_round_trip_stays_on_its_device():
    """A host_offload stash (a vjp closure) lands in its own device's
    pinned_host memory and comes back to that device bit-identical."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.memory import offload

    x = jnp.linspace(-2.0, 2.0, 64, dtype=jnp.float32).reshape(8, 8)
    out, vjp_fn = jax.vjp(jax.jit(lambda a: jnp.tanh(a @ a) * 3.0), x)
    leaves = jax.tree.leaves(vjp_fn)
    assert leaves
    host = offload.to_host(vjp_fn)
    for a, h in zip(leaves, jax.tree.leaves(host)):
        assert h.sharding.memory_kind == "pinned_host"
        assert h.sharding.device_set == a.sharding.device_set
    back = offload.to_device(host)
    for a, d in zip(leaves, jax.tree.leaves(back)):
        assert d.sharding.memory_kind == a.sharding.memory_kind
        assert d.sharding.device_set == a.sharding.device_set
        assert np.array_equal(np.asarray(d), np.asarray(a))
    ct = jnp.ones_like(out)
    assert np.array_equal(np.asarray(back(ct)[0]), np.asarray(vjp_fn(ct)[0]))


@pytest.mark.parametrize("kind,v,residency", [
    ("1f1b_interleaved", 2, "none"),
    ("bpipe_interleaved", 2, "none"),
    ("1f1b_interleaved", 2, "host_offload"),
])
def test_executor_bytes_agree_with_memory_model(exec_setup, kind, v,
                                                residency):
    """Satellite regression: the store charges the SAME v-chunk unit
    weighting as memory_model.act_bytes_per_stage, so executor-reported
    peak_bytes / bytes_moved agree with the model's per-stage numbers
    for interleaved kinds (peaks compared where the live store reaches
    the compiled bound)."""
    from repro.pipeline import PipelineExecutor
    cfg, params, batch, _ = exec_setup
    spec = P.ScheduleSpec(kind, 4, 8, v=v, residency=residency)
    ex = PipelineExecutor(cfg, spec=spec, micro_batch=1)
    r = ex.step(params, batch)
    seq = batch["tokens"].shape[1]
    n = Notation(a=cfg.num_heads, b=1, h=cfg.d_model, l=cfg.num_layers,
                 s=seq, v=cfg.vocab_size, B=8, p=4, t=1)
    unit = MM.act_bytes_per_stage(n, "none", v)
    mems = MM.per_stage_memory(n, "none", spec)
    sch = P.compile_plan(spec)
    retained = spec.policy.retained_bytes(n, "none", v)
    for i in range(4):
        # per-chunk weighting: live peak bytes = live peak units x the
        # model's unit bytes (+ retained bytes of spilled units)
        assert r.stats.peak_bytes[i] <= mems[i].act_bytes + unit
        if r.stats.peak_local[i] == sch.peak_stash[i]:
            assert r.stats.peak_bytes[i] == pytest.approx(
                mems[i].act_bytes - sch.peak_spilled.get(i, 0) * retained,
                rel=1e-6, abs=unit * 0.51)
    # traffic agreement is exact: moves x unit bytes
    assert r.stats.bytes_moved == pytest.approx(
        MM.traffic_bytes(n, "none", spec))


def test_store_per_chunk_weighting():
    """The store accepts per-(owner, chunk) weights and charges moves /
    peaks with them (the hook non-uniform layer assignments plug into)."""
    w = {(0, 0): 10.0, (0, 1): 1.0, (1, 0): 5.0, (1, 1): 5.0}
    st = ActivationStore(2, lambda stage, chunk: w[(stage, chunk)])
    st.put(0, 0, "a", chunk=0)
    st.put(0, 0, "b", chunk=1)
    assert st.peak_bytes[0] == 11.0
    st.evict(0, 0, partner=1, chunk=0)      # 10 bytes move to stage 1
    assert st.bytes_moved == 10.0
    assert st.cur_bytes[0] == 1.0 and st.cur_bytes[1] == 10.0
    st.load(0, 0, partner=1, chunk=0)
    assert st.bytes_moved == 20.0 and st.peak_bytes[0] == 11.0


# ---------------------------------------------------------------------------
# Planner: residency searched jointly with (kind, v, b, m, cap)
# ---------------------------------------------------------------------------
def _notation():
    return Notation(a=4, b=1, h=256, l=16, s=128, v=512, B=16, p=4, t=1)


def test_search_space_enumerates_residency_with_cap_ladder():
    from repro.planner import SearchSpace
    from repro.planner.space import enumerate_candidates
    n = _notation()
    cands = list(enumerate_candidates(
        n, SearchSpace(kinds=("1f1b", "bpipe"), attentions=("recompute",),
                       vs=(2,))))
    res = {c.residency for c in cands}
    assert res == {"none", "bpipe_swap", "host_offload",
                   "selective_recompute"}
    # active residency opens its own cap ladder on the PLAIN kind
    offload_caps = {c.cap for c in cands
                    if c.residency == "host_offload" and c.kind == "1f1b"}
    assert len(offload_caps) > 1
    # every candidate spec-compiles
    for c in cands:
        P.compile_plan(c.spec(n.p))


def test_managed_plans_face_break_even_and_ties_prefer_less_traffic():
    from repro.planner import AnalyticCostModel, SearchSpace
    from repro.planner import rank as R
    from repro.planner.space import enumerate_candidates
    n = _notation()
    hbm = 1.2 * MM.max_stage_bytes(n, "recompute", "1f1b")
    ranked = R.rank(n, enumerate_candidates(
        n, SearchSpace(vs=(2,), attentions=("recompute",))),
        AnalyticCostModel(), hbm, workspace=0.0)
    managed = [rp for rp in ranked
               if rp.cand.residency not in ("none",) and rp.ok]
    assert managed, "no managed plan survived"
    # every surviving managed plan carries the break-even bar vs the
    # unmanaged 1f1b baseline (or the arm has no such baseline)
    for rp in managed:
        assert rp.required_gain > 0 or "baseline" in rp.note
    # equal-MFU ties resolve toward less residency move time
    for a, b_ in zip(ranked, ranked[1:]):
        if a.verdict == b_.verdict == "ok" and a.mfu == b_.mfu:
            assert a.move_time <= b_.move_time


def test_custom_policy_registers_end_to_end(exec_setup):
    """Registering a ResidencyPolicy is the ONE step: its ops compile
    (dep edges + accounting), simulate (priced by mechanism), EXECUTE
    (handlers derived from the registry), and the spec dimension
    accepts it."""
    from repro.pipeline import PipelineExecutor
    pol = respol.ResidencyPolicy(
        "nvme_offload", "NVME_OUT", "NVME_IN", mechanism="host",
        default_cap=respol.residency_cap,
        cap_roof=respol.residency_cap_roof)
    respol.register(pol)
    try:
        with pytest.raises(ValueError, match="already registered"):
            respol.register(pol)
        spec = P.ScheduleSpec("1f1b", 4, 8, residency="nvme_offload")
        sch = P.compile_plan(spec)
        assert any(x.op == "NVME_OUT" for x in sch.streams[0])
        twin = P.compile_plan(
            P.ScheduleSpec("1f1b", 4, 8, residency="host_offload"))
        assert sch.peak_stash == twin.peak_stash
        res = SIM.simulate(SIM.SimConfig(
            spec=spec, Tf=1.0, Tb=2.0, evict_bytes=4.0,
            d2h_bw=1.0, h2d_bw=1.0))
        ref = SIM.simulate(SIM.SimConfig(
            spec=P.ScheduleSpec("1f1b", 4, 8, residency="host_offload"),
            Tf=1.0, Tb=2.0, evict_bytes=4.0, d2h_bw=1.0, h2d_bw=1.0))
        assert res.makespan == ref.makespan
        # executable with no interpreter edits: handlers come from the
        # registry, not a hard-coded op list
        cfg, params, batch, ref_loss = exec_setup
        ex = PipelineExecutor(cfg, spec=spec, micro_batch=1)
        r = ex.step(params, batch)
        assert abs(float(r.loss - ref_loss)) < 1e-5
        assert r.stats.offloads == r.stats.fetches > 0
    finally:
        respol.unregister("nvme_offload")
    with pytest.raises(ValueError, match="unknown residency"):
        P.ScheduleSpec("1f1b", 4, 8, residency="nvme_offload")


def test_fit_trace_tolerates_residency_ops():
    from repro.obs import events as OE
    from repro.planner import calibrate
    events = [OE.make(F, 0, 0, start=0.0, end=1.0),
              OE.make(OFFLOAD, 0, 0, start=1.0, end=1.5),
              OE.make(RECOMPUTE, 0, 0, start=1.5, end=2.0),
              OE.make(B, 0, 0, start=2.0, end=4.0)]
    fit = calibrate.fit_trace(events)
    assert (fit.Tf, fit.Tb) == (1.0, 2.0) and fit.samples == 4


def test_policy_op_collision_rejected():
    with pytest.raises(ValueError, match="collide"):
        respol.register(respol.ResidencyPolicy(
            "evil", OFFLOAD, "OTHER", mechanism="host",
            default_cap=respol.residency_cap,
            cap_roof=respol.residency_cap_roof))
    with pytest.raises(ValueError, match="need release_op"):
        respol.ResidencyPolicy("half", "REL", None, mechanism="host")
