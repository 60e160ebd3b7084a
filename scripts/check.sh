#!/usr/bin/env bash
# Offline-safe repo check: byte-compile everything, then run tier-1.
#
#   scripts/check.sh            # full tier-1 (includes slow tests)
#   scripts/check.sh -m 'not slow'   # extra pytest args pass through
#
# Needs no network and no PYTHONPATH fiddling (pyproject sets
# pythonpath=["src"]); the property tests need hypothesis
# (requirements-dev.txt).
#
# Env knobs:
#   REPRO_FUZZ_EXAMPLES       differential-harness simulator examples (200)
#   REPRO_FUZZ_EXEC_EXAMPLES  differential-harness executor examples (6)
#   REPRO_TEST_BUDGET_S       per-test duration budget for the grep below
#                             (default 120 here for slow dev boxes; CI
#                             pins 30 so a tier-1 test cannot silently
#                             regress past 30s on a standard runner)
set -euo pipefail
cd "$(dirname "$0")/.."

python -m compileall -q src benchmarks examples tests

# Architecture guard: exactly ONE ready-instruction dispatch loop exists
# (plan.run). A second "while remaining" loop means a module grew its own
# scheduler again — the regression the compiled-plan refactor removed.
loops=$(grep -rl --include='*.py' "while remaining" src/repro)
if [ "$loops" != "src/repro/core/plan.py" ]; then
    echo "ready-loop guard failed: expected only src/repro/core/plan.py," >&2
    echo "found: $loops" >&2
    exit 1
fi

# Observability guard: exactly ONE module constructs trace spans
# (obs/events.py — engines emit through Observer.emit, exporters rebuild
# through the factory helpers). A second "Span(" constructor means a
# side-channel trace schema grew back — the drift the unified event
# stream removed (docs/observability.md).
spans=$(grep -rl --include='*.py' "Span(" src/repro)
if [ "$spans" != "src/repro/obs/events.py" ]; then
    echo "span-emission guard failed: expected only src/repro/obs/events.py," >&2
    echo "found: $spans" >&2
    exit 1
fi

# Differential schedule-fuzz harness, seeded + bounded: random valid
# ScheduleSpecs must keep the executor bit-identical to unmanaged
# execution, the simulator above the ideal bound / engine-order
# invariant, and executor bytes agreeing with the memory model. A
# failing spec is shrunk and written to fuzz_failures.json (CI uploads
# it), so a red run reproduces from the JSON alone.
rm -f fuzz_failures.json
REPRO_FUZZ_EXAMPLES="${REPRO_FUZZ_EXAMPLES:-200}" \
REPRO_FUZZ_EXEC_EXAMPLES="${REPRO_FUZZ_EXEC_EXAMPLES:-6}" \
    python -m pytest -q tests/test_differential.py

# Benchmark suite on tiny CPU-only shapes (includes the planner sweep
# over the two smallest configs and the long-context slicing sweep) —
# schedule/planner regressions fail here, not just in tier-1. The
# tracked copy under benchmarks/ records the smoke trajectory in-repo;
# a diff on it in review IS the perf report.
PYTHONPATH=src python -m benchmarks.run --smoke > /dev/null
cp BENCH_smoke.json benchmarks/BENCH_smoke.json

# Slicing must not perturb the baseline engine: every unsliced golden
# case's makespan is recomputed from a fresh compile and compared
# against the pinned fixture — seq_chunks=1 stays bit-identical.
PYTHONPATH=src python - <<'PYEOF'
import json
import repro.core.plan as P
import repro.core.simulator as SIM
cases = [c for c in json.load(open("tests/golden/plan_golden.json"))
         if "residency" not in c and c.get("seq_chunks", 1) == 1]
assert len(cases) == 30, f"unsliced golden census changed: {len(cases)}"
for c in cases:
    spec = P.ScheduleSpec(c["kind"], c["p"], c["m"],
                          v=max(c["v"], 1), cap=c["cap"])
    res = SIM.simulate(SIM.SimConfig(
        spec=spec, Tf=1.0, Tb=2.0, t_p2p=0.125,
        evict_bytes=1.0, pair_bw=2.0, pair_hops=1))
    assert res.makespan == c["makespan"], (
        f"seq_chunks=1 makespan drifted for {spec.label()}: "
        f"{res.makespan} != {c['makespan']}")
print("golden seq_chunks=1 makespans unchanged (30 cases)")
PYEOF

# Planner acceptance verdicts (paper Table 3): BPipe must win
# GPT-3-recompute and lose LLaMA. (Captured first, then grepped:
# `cli | grep -q` races — grep exits at the first match and SIGPIPEs
# the still-printing CLI, which pipefail turns into a flaky failure.)
# The winning plan's simulated timeline is exported alongside the
# verdict (one event schema end to end — the same CLI answers "which
# plan" and "what does its step look like"); CI uploads the trace on
# failure so a red verdict arrives with its timeline attached.
gpt3_out=$(PYTHONPATH=src python -m repro.launch.plan --config gpt3_96b \
    --attention recompute --top 0 --perfetto plan_trace.perfetto.json \
    --metrics-json plan_metrics.json)
grep -q 'PLAN gpt3-96b \[recompute\]: bpipe' <<< "$gpt3_out"
test -s plan_trace.perfetto.json
test -s plan_metrics.json
llama_out=$(PYTHONPATH=src python -m repro.launch.plan --config llama_65b \
    --top 0)
grep -q 'PLAN llama-65b: 1f1b' <<< "$llama_out"

# Vocab-parallel verdict (docs/memory.md "Vocab accounting"): at 14 GiB
# the 151k-vocab qwen3-14b is infeasible unscattered — opening the vp
# ladder must recover a vp=4 BPipe plan. The Table 3 greps above run
# with the default (unscattered) space, so they double as the
# vocab_parallel=1 no-change guard.
qwen_out=$(PYTHONPATH=src python -m repro.launch.plan --config qwen3_14b \
    --attention recompute --hbm-gb 14 --vocab-parallel 1 2 4 8 --top 0)
grep -q 'PLAN qwen3-14b \[recompute\]: bpipe .*vp=4' <<< "$qwen_out"

# Planner-speed gate: the branch-and-bound search must keep the FULL
# 13-config sweep fast (the perf_opt this repo ships — see
# docs/planner.md "Search performance"). Budget is generous vs the ~7s
# measured so slow CI boxes pass, but a pruning regression that falls
# back to exhaustive-scale work (~42s at HEAD before the B&B search)
# fails loudly. Counters print alongside so a red run says what the
# search did.
speed_budget="${REPRO_PLANNER_SWEEP_BUDGET_S:-25}"
PYTHONPATH=src python - "$speed_budget" <<'PYEOF'
import sys, time
from benchmarks import planner_sweep
budget = float(sys.argv[1])
t0 = time.perf_counter()
planner_sweep.main(print_csv=False, smoke=False)
dt = time.perf_counter() - t0
m = planner_sweep.LAST_METRICS
print(f"planner sweep: {dt:.2f}s over 13 configs — "
      f"{m['enumerated']} enumerated, {m['simulated']} simulated, "
      f"{m['pruned']} pruned (budget {budget:.0f}s)")
assert dt <= budget, (
    f"planner sweep took {dt:.2f}s > {budget:.0f}s budget — "
    f"branch-and-bound pruning regressed?")
PYEOF

# Tier-1 with a per-test wall-clock budget: --durations surfaces the
# slowest tests and the awk grep fails the run if any single test
# exceeds the budget — a silent 10x slowdown in one test is a
# regression even while green. Exemptions: the differential harness
# already ran (seeded + bounded) above, and slow-MARKED tests are
# declared slow, not silently slow — they run un-budgeted afterwards.
# (pytest exit 5 = "no tests collected" — fine in either phase when
# pass-through args select only slow, or only non-slow, tests)
budget="${REPRO_TEST_BUDGET_S:-120}"
durations_log=$(mktemp)
fast_rc=0
python -m pytest -q --durations=10 -m "not slow" \
    --ignore=tests/test_differential.py "$@" \
    | tee "$durations_log" || fast_rc=$?
[ "$fast_rc" -eq 0 ] || [ "$fast_rc" -eq 5 ]
awk -v budget="$budget" '
    /^[0-9.]+s (call|setup|teardown)/ {
        if ($1 + 0 > budget) { print "over budget (" budget "s):", $0; bad = 1 }
    }
    END { exit bad }
' "$durations_log"
rm -f "$durations_log"
slow_rc=0
python -m pytest -q -m "slow" --ignore=tests/test_differential.py "$@" \
    || slow_rc=$?
[ "$slow_rc" -eq 0 ] || [ "$slow_rc" -eq 5 ]
