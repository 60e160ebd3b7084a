"""Differential schedule-fuzz harness.

Property-based over random *valid* ``ScheduleSpec``s — all kinds x
residency x cap x v x overlap depth, drawn through hypothesis
strategies (hypothesis replays a failing example from its example
database, so failures reproduce run-to-run):

  (a) executor loss/grads are bit-identical to the unmanaged execution
      of the same schedule family (residency moves must never change
      what is computed) and match the non-pipelined single-device
      reference to fp32 tolerance;
  (b) simulator makespan respects the ideal pipeline lower bound, is
      invariant under greedy vs round-robin engine order for every
      single-issuer-channel spec (all built-in policies at default
      caps), and is monotone non-increasing in overlap depth;
  (c) executor ``peak_bytes``/``bytes_moved`` agree with
      ``memory_model``'s per-stage accounting.

Failing specs are greedily *shrunk* (m, p, v, depth, cap toward
minimal while the property still fails) and reported as spec JSON —
also appended to ``fuzz_failures.json`` (``REPRO_FUZZ_ARTIFACT``) so CI
can upload the counterexample as an artifact.

Example counts are env-tunable (``scripts/check.sh`` pins them):
``REPRO_FUZZ_EXAMPLES`` for the cheap simulator properties (default
200), ``REPRO_FUZZ_EXEC_EXAMPLES`` for the jax-compiling executor
properties (default 3 — scripts/check.sh's dedicated harness step pins
6). The executor properties are also ``slow``-MARKED: each example
jit-compiles a real pipeline step, so a plain ``pytest -m 'not slow'``
sweep skips them and the harness step (or ``-m slow``) owns them.
"""
import dataclasses
import json
import os

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import memory_model as MM
from repro.core import plan as P
from repro.core import schedule as S
from repro.core import simulator as SIM
from repro.core.notation import Notation
from repro.memory import policy as respol
from repro.transfer.channel import channel_key

FUZZ_EXAMPLES = int(os.environ.get("REPRO_FUZZ_EXAMPLES", "200"))
FUZZ_EXEC_EXAMPLES = int(os.environ.get("REPRO_FUZZ_EXEC_EXAMPLES", "3"))
ARTIFACT = os.environ.get("REPRO_FUZZ_ARTIFACT", "fuzz_failures.json")

KINDS = ("gpipe", "1f1b", "bpipe", "1f1b_interleaved", "bpipe_interleaved")
RESIDENCIES = ("none", "host_offload", "selective_recompute")


# ---------------------------------------------------------------------------
# Spec strategy: every draw is a structurally valid ScheduleSpec
# ---------------------------------------------------------------------------
def build_spec(kind_i: int, p: int, m_mult: int, v: int, res_i: int,
               cap_delta: int, depth: int,
               seq_chunks: int = 1) -> P.ScheduleSpec:
    kind = KINDS[kind_i % len(KINDS)]
    entry = S.SCHEDULES[kind]
    if entry.interleaved:
        v = max(2, v)
        m = p * max(1, m_mult)        # m % p == 0
    else:
        v = 1
        m = max(1, m_mult * 2)
    if not entry.sliced:
        seq_chunks = 1                # the spec would normalize anyway
    if entry.balanced:
        res = "none"                   # normalizes to bpipe_swap
        default, roof = entry.default_cap(p, v), entry.cap_roof(p, m, v)
    else:
        res = RESIDENCIES[res_i % len(RESIDENCIES)]
        pol = respol.POLICIES[res]
        default = pol.default_cap(p, v) if pol.active else None
        roof = pol.cap_roof(p, m, v) if pol.active else None
    cap = None
    if default is not None and cap_delta:
        # sliced defaults widen by the extra warmup slices; keep the
        # delta centered there so -1 still bites
        default += seq_chunks - 1
        roof += seq_chunks - 1
        cap = min(max(default + cap_delta, 2), max(roof, 2))
        if cap == default:
            cap = None
    return P.ScheduleSpec(kind, p, m, v=v, cap=cap, residency=res,
                          depth=depth, seq_chunks=seq_chunks)


spec_strategy = st.tuples(
    st.integers(0, len(KINDS) - 1),   # kind
    st.integers(2, 6),                # p
    st.integers(1, 3),                # m multiplier
    st.integers(2, 3),                # v (interleaved kinds)
    st.integers(0, len(RESIDENCIES) - 1),
    st.integers(-1, 1),               # cap delta around the default
    st.integers(1, 3),                # overlap depth
    st.sampled_from([1, 1, 2, 4]),    # seq_chunks (sliced kinds)
).map(lambda t: build_spec(*t))

cost_strategy = st.floats(0.0, 4.0)   # evict_bytes (bandwidths fixed at 1)


def _report(spec: P.ScheduleSpec, prop: str, detail: str) -> str:
    """Persist the failing spec for the CI artifact and build the
    assertion message (the spec JSON *is* the repro recipe)."""
    rec = {"property": prop, "spec": spec.to_dict(), "detail": detail}
    try:
        existing = []
        if os.path.exists(ARTIFACT):
            with open(ARTIFACT) as f:
                existing = json.load(f)
        existing.append(rec)
        with open(ARTIFACT, "w") as f:
            json.dump(existing, f, indent=1)
    except OSError:
        pass
    return f"[{prop}] failing spec {json.dumps(spec.to_dict())}: {detail}"


def shrink_spec(spec: P.ScheduleSpec, fails) -> P.ScheduleSpec:
    """Greedy shrink: repeatedly try the reductions (smaller m, p, v,
    depth; drop the cap override) and keep any that still fails, until
    a fixpoint — the counterexample reported is minimal under these
    moves."""
    def candidates(s):
        if s.m > s.p:
            yield dataclasses.replace(s, m=max(s.p, s.m // 2))
        if not s.interleaved and s.m > 1:
            yield dataclasses.replace(s, m=s.m - 1)
        if s.p > 2:
            p2 = s.p // 2 if s.p % 2 == 0 else s.p - 1
            m2 = s.m if not s.interleaved else (s.m // s.p) * p2
            try:
                yield P.ScheduleSpec(s.kind, p2, max(m2, p2), v=s.v,
                                     cap=None, residency=s.residency,
                                     depth=s.depth)
            except ValueError:
                pass
        if s.v > 2 and s.interleaved:
            yield dataclasses.replace(s, v=s.v - 1)
        if s.seq_chunks > 1:
            yield dataclasses.replace(s, seq_chunks=s.seq_chunks // 2)
        if s.depth > 1:
            yield dataclasses.replace(s, depth=s.depth - 1)
        if s.cap is not None:
            yield dataclasses.replace(s, cap=None)

    for _ in range(16):
        for cand in candidates(spec):
            try:
                if fails(cand):
                    spec = cand
                    break
            except Exception:      # noqa: BLE001 — a crash also "fails"
                spec = cand
                break
        else:
            return spec
    return spec


def _compiles(spec: P.ScheduleSpec) -> bool:
    """Tight sampled caps can be unbalanceable at some (p, m, v); those
    are invalid points of the space (the planner prunes them), not
    counterexamples."""
    try:
        P.compile_plan(spec)
        return True
    except (AssertionError, IndexError, ValueError):
        return False


def _issuers_per_channel(sch) -> dict:
    out = {}
    for i, stream in sch.streams.items():
        for x in stream:
            if x.is_wait:
                continue
            pol = respol.RELEASE_OPS.get(x.op) or respol.RESTORE_OPS.get(x.op)
            if pol is None:
                continue
            key = channel_key(pol.mechanism, i, sch.partner.get(i),
                              x.op in respol.RELEASE_OPS)
            if key is not None:
                out.setdefault(key, set()).add(i)
    return out


# ---------------------------------------------------------------------------
# (b) simulator: ideal bound, engine-order invariance, depth monotone
# ---------------------------------------------------------------------------
def _sim(spec, evict_bytes, greedy=True):
    return SIM.simulate(SIM.SimConfig(
        spec=spec, Tf=1.0, Tb=2.0, evict_bytes=evict_bytes,
        pair_bw=1.0, d2h_bw=1.0, h2d_bw=1.0), greedy=greedy)


@given(spec_strategy, cost_strategy)
@settings(max_examples=FUZZ_EXAMPLES, deadline=None)
def test_simulator_bound_order_and_depth(spec, evict_bytes):
    if not _compiles(spec):
        return

    def violates(s):
        r = _sim(s, evict_bytes)
        # interleaving shrinks the fill/drain ramp by v, slicing by c
        # (per-slice F/B cost Tf/c, Tb/c) — v and c never both exceed 1
        ramp = (s.p - 1) / (s.v * s.seq_chunks)
        ideal = (s.m + ramp) * 3.0        # (m + ramp)(Tf + Tb)
        if r.makespan < ideal - 1e-9:
            return "makespan below the ideal pipeline bound " \
                f"({r.makespan} < {ideal})"
        if r.makespan < max(r.busy) - 1e-9:
            return "makespan below a stage's own busy time"
        if r.queue_peak > s.depth:
            return (f"channel occupancy {r.queue_peak} exceeds depth "
                    f"{s.depth}")
        sch = P.compile_plan(s)
        single = all(len(v_) == 1 for v_ in _issuers_per_channel(sch)
                     .values())
        if single:
            rr = _sim(s, evict_bytes, greedy=False)
            if rr.makespan != r.makespan or rr.timeline != r.timeline:
                return (f"engine-order variant: greedy {r.makespan} != "
                        f"round-robin {rr.makespan}")
        if s.policy.moves_data or s.balanced:
            deeper = _sim(dataclasses.replace(s, depth=s.depth + 1),
                          evict_bytes)
            if deeper.makespan > r.makespan + 1e-9:
                return (f"deeper overlap hurt: depth {s.depth} -> "
                        f"{r.makespan}, depth {s.depth + 1} -> "
                        f"{deeper.makespan}")
        return None

    why = violates(spec)
    if why is not None:
        small = shrink_spec(spec, lambda s: _compiles(s)
                            and violates(s) is not None)
        raise AssertionError(_report(small, "simulator", violates(small)
                                     or why))


@given(spec_strategy)
@settings(max_examples=min(FUZZ_EXAMPLES, 60), deadline=None)
def test_compiled_plan_self_consistency(spec):
    """Structural invariants of the compiled IR, fuzzed: every move has
    matching ISSUE/WAIT halves, the collapsed view is move-balanced, and
    the per-stage counts agree with the accounting."""
    if not _compiles(spec):
        return
    sch = P.compile_plan(spec)
    for i, stream in sch.streams.items():
        issues = [x for x in stream if x.phase == P.ISSUE]
        waits = [x for x in stream if x.is_wait]
        assert len(issues) == len(waits), (spec.to_dict(), i)
        assert {x.done_key for x in issues} == {x.done_key for x in waits}
        rel = sum(1 for x in issues if x.op in respol.RELEASE_OPS)
        res_ = sum(1 for x in issues if x.op in respol.RESTORE_OPS)
        assert rel == sch.num_evictions[i] and res_ == sch.num_loads[i], \
            _report(spec, "plan", f"stage {i} move counts disagree")
        # restores follow their release in stream order
        seen = set()
        for x in stream:
            if x.is_wait:
                continue
            if x.op in respol.RELEASE_OPS:
                seen.add((x.mb, x.chunk))
            elif x.op in respol.RESTORE_OPS:
                assert (x.mb, x.chunk) in seen, \
                    _report(spec, "plan", f"orphan restore {x!r}")


# ---------------------------------------------------------------------------
# (a) + (c) executor: numerics and byte agreement
# ---------------------------------------------------------------------------
def _exec_specs():
    """Structurally valid specs a 4-layer model can execute (p*v <= 4,
    m=4): the full kind x residency x cap x depth cross section, plus
    the sequence-sliced variants (c divides the batch's seq=8; sliced
    runs stay at the default cap — the cap ladder is already covered
    unsliced and each extra executor spec is a jit compile)."""
    out = []
    for kind, p, v, c in (("gpipe", 2, 1, 1), ("1f1b", 4, 1, 1),
                          ("bpipe", 4, 1, 1),
                          ("1f1b_interleaved", 2, 2, 1),
                          ("bpipe_interleaved", 2, 2, 1),
                          ("gpipe", 2, 1, 2), ("1f1b", 4, 1, 2),
                          ("bpipe", 4, 1, 2), ("1f1b", 2, 1, 4)):
        entry = S.SCHEDULES[kind]
        residencies = ("none",) if entry.balanced else RESIDENCIES
        for res in residencies:
            pol = respol.POLICIES[res]
            managed = entry.balanced or pol.active
            if entry.balanced:
                default = entry.default_cap(p, v)
            elif pol.active:
                default = pol.default_cap(p, v)
            for cap_delta in (0, -1):
                if cap_delta and (not managed or c > 1):
                    continue
                cap = None if not cap_delta else max(default + cap_delta, 2)
                for depth in (1, 2):
                    try:
                        spec = P.ScheduleSpec(kind, p, 4, v=v, cap=cap,
                                              residency=res, depth=depth,
                                              seq_chunks=c)
                    except ValueError:
                        continue
                    if not _compiles(spec):
                        continue
                    if spec not in out:
                        out.append(spec)
    return out


_EXEC_CACHE = {}


def _exec_step(spec):
    import jax
    from repro.configs import get_config
    from repro.models import model as M
    from repro.pipeline import PipelineExecutor
    if "setup" not in _EXEC_CACHE:
        cfg = dataclasses.replace(get_config("qwen1.5-0.5b").reduced(),
                                  num_layers=4, dtype="float32")
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(7), (4, 9), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        ref_loss, _ = M.loss_fn(params, batch, cfg)
        _EXEC_CACHE["setup"] = (cfg, params, batch, float(ref_loss))
    cfg, params, batch, ref_loss = _EXEC_CACHE["setup"]
    if spec not in _EXEC_CACHE:
        ex = PipelineExecutor(cfg, spec=spec, micro_batch=1)
        _EXEC_CACHE[spec] = ex.step(params, batch)
    return _EXEC_CACHE[spec], ref_loss


def _unmanaged_twin(spec: P.ScheduleSpec) -> P.ScheduleSpec:
    kind = {"bpipe": "1f1b",
            "bpipe_interleaved": "1f1b_interleaved"}.get(spec.kind,
                                                         spec.kind)
    return P.ScheduleSpec(kind, spec.p, spec.m, v=spec.v,
                          seq_chunks=spec.seq_chunks)


@pytest.mark.slow
@given(st.sampled_from(_exec_specs()))
@settings(max_examples=FUZZ_EXEC_EXAMPLES, deadline=None)
def test_executor_differential_vs_unmanaged(spec):
    import jax
    import numpy as np
    r, ref_loss = _exec_step(spec)
    base, _ = _exec_step(_unmanaged_twin(spec))
    # fp32 contract vs the non-pipelined single-device reference
    assert abs(float(r.loss) - ref_loss) < 1e-5, \
        _report(spec, "executor", f"loss {float(r.loss)} != ref {ref_loss}")
    # bit-identical to the unmanaged execution: residency moves relocate
    # the stash, they must never change what is computed
    assert float(r.loss) == float(base.loss), \
        _report(spec, "executor", "loss != unmanaged twin")
    for a, b in zip(jax.tree.leaves(r.grads), jax.tree.leaves(base.grads)):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            raise AssertionError(_report(spec, "executor",
                                         "grads != unmanaged twin"))


@pytest.mark.slow
@given(st.sampled_from([s for s in _exec_specs() if s.seq_chunks > 1]))
@settings(max_examples=min(FUZZ_EXEC_EXAMPLES, 4), deadline=None)
def test_executor_sliced_parity_vs_unchunked(spec):
    """A sliced schedule computes the SAME training step as its
    unchunked twin — same loss, same grads — to fp32 tolerance (exact
    bit-parity is not expected: slice-wise softmax/vjp re-associates
    reductions)."""
    import jax
    import numpy as np
    r, _ = _exec_step(spec)
    twin = _unmanaged_twin(spec)
    base, _ = _exec_step(P.ScheduleSpec(twin.kind, twin.p, twin.m,
                                        v=twin.v))
    assert abs(float(r.loss) - float(base.loss)) < 1e-5, \
        _report(spec, "executor-sliced",
                f"loss {float(r.loss)} != unchunked {float(base.loss)}")
    for a, b in zip(jax.tree.leaves(r.grads), jax.tree.leaves(base.grads)):
        if not np.allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                           atol=1e-5):
            raise AssertionError(_report(spec, "executor-sliced",
                                         "grads drift vs unchunked twin"))


@pytest.mark.slow
@given(st.sampled_from(_exec_specs()))
@settings(max_examples=FUZZ_EXEC_EXAMPLES, deadline=None)
def test_executor_bytes_agree_with_memory_model(spec):
    r, _ = _exec_step(spec)
    cfg, params, batch, _ = _EXEC_CACHE["setup"]
    seq = batch["tokens"].shape[1]
    n = Notation(a=cfg.num_heads, b=1, h=cfg.d_model, l=cfg.num_layers,
                 s=seq, v=cfg.vocab_size, B=4, p=spec.p, t=1)
    sch = P.compile_plan(spec)
    unit = MM.sliced_unit_bytes(n, "none", spec.v, spec.seq_chunks)
    mems = MM.per_stage_memory(n, "none", spec)
    for i in range(spec.p):
        if r.stats.peak_local[i] > sch.peak_stash[i] + 1:
            raise AssertionError(_report(
                spec, "memory", f"stage {i} live peak "
                f"{r.stats.peak_local[i]} > compiled {sch.peak_stash[i]}+1"))
        # the model's depth charge is an upper bound on the live bytes
        if r.stats.peak_bytes[i] > mems[i].act_bytes + unit + 1e-6:
            raise AssertionError(_report(
                spec, "memory", f"stage {i} peak bytes exceed the model"))
    want = MM.traffic_bytes(n, "none", spec)
    if abs(r.stats.bytes_moved - want) > 1e-6 * max(want, 1.0):
        raise AssertionError(_report(
            spec, "memory",
            f"bytes_moved {r.stats.bytes_moved} != model {want}"))
    assert r.stats.transfers_inflight_peak <= spec.depth, \
        _report(spec, "memory", "in-flight transfers exceed the depth cap")


@pytest.mark.slow
@given(st.sampled_from(_exec_specs()))
@settings(max_examples=min(FUZZ_EXEC_EXAMPLES, 4), deadline=None)
def test_executor_and_simulator_emit_same_instruction_set(spec):
    """Observability census invariant (docs/observability.md): the
    simulator's and the real executor's event streams for the SAME spec
    contain the same instruction set — every key the model prices is
    executed and vice versa (timing and dispatch order may differ;
    ``obs.compare`` quantifies those separately)."""
    from repro.obs.events import Recorder
    key = (spec, "events")
    if key not in _EXEC_CACHE:
        from repro.pipeline import PipelineExecutor
        _exec_step(spec)                  # ensures the shared setup
        cfg, params, batch, _ = _EXEC_CACHE["setup"]
        ex = PipelineExecutor(cfg, spec=spec, micro_batch=1)
        _EXEC_CACHE[key] = ex.step(params, batch, trace=True).events
    real_keys = {s.key for s in _EXEC_CACHE[key] if s.track == "compute"}
    rec = Recorder()
    SIM.simulate(SIM.SimConfig(spec=spec, Tf=1.0, Tb=2.0, evict_bytes=1.0,
                               pair_bw=1.0, d2h_bw=1.0, h2d_bw=1.0),
                 observer=rec)
    if rec.keys() != real_keys:
        diff = sorted(rec.keys() ^ real_keys)
        raise AssertionError(_report(
            spec, "observability",
            f"sim/executor instruction sets differ on {len(diff)} keys: "
            f"{diff[:6]}"))
