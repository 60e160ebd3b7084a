"""Executable pipeline runtime: a schedule interpreter with true 1F1B /
BPipe activation-stash semantics, chunk-aware for interleaved schedules.

This is the Megatron-equivalent layer of the reproduction: a compiled
``plan.Schedule`` is interpreted instruction-by-instruction as a handler
set over the shared dispatch engine (``plan.run``); each F runs
``jax.vjp`` on its (virtual) stage (so the stash — the vjp residuals — is
*really* held until the matching B), EVICT/LOAD move stash entries between
the evictor's and acceptor's stores (on one host this is bookkeeping plus
the byte accounting from ``core.memory_model``; on a multi-device host it
would be a device_put), and every B consumes its stash and propagates the
cotangent upstream.

Residency policies (``repro.memory``, a ``ScheduleSpec`` dimension) give
the stash other places to live: OFFLOAD/FETCH really ``jax.device_put``
the vjp closure (a ``tree_util.Partial`` pytree) to its device's
``pinned_host`` memory and back; DROP frees the residuals keeping only the boundary input, and
RECOMPUTE re-runs the stage forward from it — both bit-identical to the
resident execution, which ``tests/test_residency.py`` pins. Every move
executes as its compiled ISSUE/WAIT halves (docs/transfer.md): the
ISSUE starts the async copy and registers it with the bounded-depth
transfer runtime (``repro.transfer.runtime``), the WAIT blocks on the
channel before the dependent compute touches the data — so the live
HBM bound holds on real in-flight buffers, not just on the store's
bookkeeping.

Interleaved kinds give each device v model chunks: chunk c on device s is
virtual stage ``c*p + s``; activations flow virtual stage vs -> vs+1 (the
hop from device p-1 back to device 0 crosses chunks), and every stash /
routing key is (stage, mb, chunk), so the same handler set executes plain
and interleaved streams. The dependency edges and partner map come
precompiled on the Schedule — the executor re-derives nothing.

Sequence-sliced schedules (``ScheduleSpec.seq_chunks`` = c > 1,
docs/longcontext.md) split every microbatch into c sequence slices:
each F runs one slice through ``make_sliced_stage_fn``, reading the
retained-KV prefix of all earlier slices via ``store.peek`` (a slice's
stash — vjp residuals plus its own post-RoPE KV — is just another store
unit, so every residency policy manages sliced KV with zero new
mechanism); each B runs in reverse slice order, accumulating the
KV-prefix gradients it emits onto the earlier slices' pending
cotangents in a single pass. At seq_chunks=1 the engine is bit-identical
to the unsliced path (pinned by tests/test_differential.py).

Profiler spans (docs/observability.md "On the chip"): every step opens
``pipeline.split``, one ``pipeline.<op>`` per executed instruction
handler (args: the span identity plus the executor's step counter),
``pipeline.grad_accum`` inside each B, and ``pipeline.merge``. They are
always entered, never block, and record only while a JAX profiler
session is active, so a device trace can put the host time of a step
down to F, B, accumulation, moves, split/merge and the interpreter
(the remainder).

Compilation contract (tested): stage fns are built and jitted once in
``__init__`` and the microbatch is a ``jax.vjp`` *argument* — not a value
closed over by a per-call lambda — so each virtual stage traces exactly
once per activation shape and repeated ``step()`` calls recompile nothing.
So is the gradient accumulator: one jitted call per B adds a whole stage
gradient pytree into the donated running sum (the first B of a stage only
stores), compiled once per virtual stage in the first step.

Numerical contract (tested): for any schedule kind,
    executor.step(params, batch).loss == models.loss_fn(params, batch)
and gradients match to fp32 tolerance. BPipe's cap (``bpipe_cap`` /
``bpipe_interleaved_cap``) is asserted on the live store, not on paper.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import memory_model as mm
from repro.core import plan as P
from repro.core import schedule as sched
from repro.core.notation import Notation
from repro.core.schedule import B, F
from repro.memory import offload as mem_offload
from repro.memory import policy as respol
# The store is re-homed to repro.memory.store; re-exported here for
# legacy importers of the executor module.
from repro.memory.store import ActivationStore, StoreStats, Unit
from repro.models import blocks as blocks_mod
from repro.obs.events import Observer, Recorder, Span, profile_span
from repro.pipeline import stage as stage_mod
from repro.transfer.channel import channel_key
from repro.transfer.runtime import AsyncTransferRuntime


@dataclasses.dataclass
class StepResult:
    loss: jnp.ndarray
    grads: Any
    stats: StoreStats
    # Canonical-schema spans (repro.obs.events.Span) of the traced step,
    # wall-clock seconds relative to step start: stage instructions
    # (WAIT halves carry phase="wait") plus channel-occupancy spans from
    # the transfer runtime, each stage span sampling the store's live
    # resident bytes (Span.hbm). None unless step(trace=True).
    events: Optional[List[Span]] = None


class PipelineExecutor:
    """Interprets a pipeline schedule over a real model.

    Preferred construction passes the schedule variant as a value:

        PipelineExecutor(cfg, spec=ScheduleSpec("bpipe", p=4), micro_batch=2)

    A spec with ``m=0`` is a template the executor binds to the real
    batch at ``step()`` (m = batch_rows / micro_batch); a bound spec
    additionally pins the expected microbatch count.

    Legacy args (deprecation shims — they construct the spec):
      p: number of pipeline stages (p * v must be <= num_layers).
      kind: any registered schedule kind (``schedule.SCHEDULES``).
      v: virtual chunks per device (interleaved kinds only; ignored
        otherwise). Interleaved streams additionally require m % p == 0.
      cap: BPipe-family / residency stash-cap override (planner-chosen).
        With a non-default cap the live assertion bounds each stage by
        the schedule's own per-stage peak accounting (a tighter evictor
        cap legitimately raises the acceptor's peak above it).
      residency: activation-residency policy for plain kinds
        (``repro.memory.policy.POLICIES``; balanced kinds embed
        ``bpipe_swap``).

    Other args:
      cfg: model config (any assigned architecture).
      micro_batch: rows per microbatch (global batch must divide evenly).
      notation: optional paper-notation override for byte accounting.
    """

    def __init__(self, cfg: ModelConfig, p: Optional[int] = None,
                 kind: str = "1f1b", micro_batch: int = 1,
                 remat: str = "none", notation: Optional[Notation] = None,
                 enforce_cap: bool = True, v: int = 2,
                 cap: Optional[int] = None,
                 residency: str = "none",
                 spec: Optional[P.ScheduleSpec] = None):
        if spec is None:
            assert p is not None, "need p (or pass spec=ScheduleSpec(...))"
            assert kind in sched.SCHEDULES, kind
            spec = P.ScheduleSpec(kind, p, 0, v=v, cap=cap,
                                  residency=residency)
        else:
            assert p is None or p == spec.p, (p, spec)
        self.spec = spec
        self.cfg, self.p, self.kind = cfg, spec.p, spec.kind
        self.v = spec.v
        self.n_virtual = spec.n_virtual
        assert self.n_virtual <= cfg.num_layers, \
            (spec.p, self.v, cfg.num_layers)
        self.b = micro_batch
        self.remat = remat
        self.enforce_cap = enforce_cap
        self.cap = spec.resolved_cap
        self.c = spec.seq_chunks
        # One jitted fn per *virtual* stage, built once: jax.vjp over a
        # stable jitted callable reuses its trace, so repeated step()
        # calls (and every microbatch within a step) compile nothing new.
        # (Sliced stage fns retrace once per distinct kv-prefix length —
        # c traces per virtual stage, still O(1) across steps.)
        if self.c > 1:
            bad = set(cfg.layer_kinds()) - set(blocks_mod.SLICEABLE_KINDS)
            assert not bad, \
                f"seq_chunks>1 needs attention mixers, got {sorted(bad)}"
            self.stage_fns = [
                jax.jit(stage_mod.make_sliced_stage_fn(
                    cfg, self.n_virtual, vs, remat))
                for vs in range(self.n_virtual)]
        else:
            self.stage_fns = [
                jax.jit(stage_mod.make_stage_fn(
                    cfg, self.n_virtual, vs, remat))
                for vs in range(self.n_virtual)]
        # One accumulator for every virtual stage (it compiles once per
        # stage gradient treedef): a whole stage's grads in one dispatch
        # instead of one eager add per leaf. Donating the running sum
        # lets XLA write into it, so a stage holds 2x its gradient bytes
        # while adding, not 3x.
        self.accumulate = jax.jit(
            lambda acc, g: jax.tree.map(jnp.add, acc, g), donate_argnums=0)
        self.splitter = stage_mod.StageSplitter(cfg, self.n_virtual)
        self.notation = notation
        self.steps = 0          # the ``step`` arg of the profiler spans

    # ------------------------------------------------------------------
    def _schedule_for(self, m: int) -> P.Schedule:
        if self.spec.bound:
            assert m == self.spec.m, \
                f"batch implies m={m} but spec binds m={self.spec.m}"
        return P.compile_plan(self.spec.with_m(m))

    def step(self, params, batch, trace: bool = False,
             observer: Optional[Observer] = None) -> StepResult:
        cfg, p = self.cfg, self.p
        nv = self.n_virtual
        bsz = batch["tokens"].shape[0]
        assert bsz % self.b == 0
        m = bsz // self.b
        seq = batch["tokens"].shape[1]
        n = self.notation or Notation(
            a=cfg.num_heads, b=self.b, h=cfg.d_model, l=cfg.num_layers,
            s=seq, v=cfg.vocab_size, B=bsz, p=p, t=1)
        attention = {"none": "none", "attn": "recompute", "full": "recompute",
                     "flash": "flash"}.get(self.remat, "none")
        policy = self.spec.policy
        c = self.c
        sliced = c > 1
        if sliced:
            assert seq % c == 0, f"seq {seq} not divisible by seq_chunks {c}"
        Ls = seq // c
        # One stash unit's bytes — the SAME v-chunk weighting
        # memory_model.act_bytes_per_stage charges, so executor-reported
        # peak_bytes/bytes_moved agree with the model's per-stage numbers
        # (each interleaved unit holds 1/v of the device's layers; a
        # sliced unit 1/c of the stage stash plus its retained-KV prefix).
        unit_bytes = mm.sliced_unit_bytes(n, attention, self.v, c)
        retained = policy.retained_bytes(n, attention, self.v)
        if sliced:
            # a released slice retains 1/c of the policy's usual bytes
            # plus its own KV (the DROP strip keeps (carry, kv_own) so
            # later slices' forwards still reach the prefix) — mirrors
            # memory_model.per_stage_memory
            retained = retained / c
            if policy.mechanism == "recompute":
                retained += mm.kv_bytes_per_slice(n, self.v, c)
        store = ActivationStore(p, unit_bytes, retained_bytes=retained)
        is_recompute = policy.mechanism == "recompute"
        swap_ops = frozenset(
            op for op, pol in {**respol.RELEASE_OPS,
                               **respol.RESTORE_OPS}.items() if pol.swap)

        step_id = self.steps
        self.steps += 1
        with profile_span("pipeline.split", step=step_id):
            stage_params = self.splitter.split(params)
        schedule = self._schedule_for(m)
        bounds = schedule.bounds
        partner = schedule.partner
        # trace=True attaches a Recorder when the caller brought no
        # observer of their own; with observer=None and trace=False the
        # step is the exact pre-instrumentation code path (zero-cost —
        # no timing, no blocking, no span construction).
        recorder: Optional[Recorder] = None
        if trace and observer is None:
            observer = recorder = Recorder()
        elif trace:
            assert isinstance(observer, Recorder), \
                "trace=True needs a Recorder observer to collect events"
            recorder = observer
        t_step0 = time.perf_counter()
        clock = lambda: time.perf_counter() - t_step0  # noqa: E731
        # In-flight transfer tracking with the spec's overlap-depth cap:
        # real copies (device_put and store moves) are async, so the
        # runtime is what makes the live HBM bound hold — at most
        # ``depth`` moves may be outstanding per channel before the
        # oldest is retired (blocked on). Same channel vocabulary the
        # simulator prices (docs/transfer.md) — and the same observer:
        # each real copy retires as a channel-track span.
        xfers = AsyncTransferRuntime(self.spec.depth, observer=observer,
                                     clock=clock)

        def chan(op: str, i: int) -> Optional[tuple]:
            pol = respol.RELEASE_OPS.get(op) or respol.RESTORE_OPS[op]
            return channel_key(pol.mechanism, i, partner.get(i),
                               release=op in respol.RELEASE_OPS)

        # Slice each microbatch once, not once per (chunk, F) — interleaving
        # visits every microbatch p*v times on this hot path.
        micros = [
            {k: val[j * self.b:(j + 1) * self.b] for k, val in batch.items()}
            for j in range(m)]

        # act_in/grad_in are keyed by the *virtual* stage they feed (plus
        # the sequence slice — 0 for unsliced schedules): the output of
        # virtual stage vs routes to vs+1, which lives on device
        # (vs+1) % p — possibly the same device, next chunk.
        act_in: Dict[Tuple[int, int, int], Any] = {}
        grad_in: Dict[Tuple[int, int, int], Any] = {}
        losses: Dict[Tuple[int, int], jnp.ndarray] = {}
        grads: List[Any] = [None] * nv
        accum_calls = 0
        dummy = (jnp.zeros((self.b, Ls, cfg.d_model), jnp.dtype(cfg.dtype)),
                 jnp.zeros((), jnp.float32))
        scale = jnp.float32(1.0 / m)

        if sliced:
            # Per-(mb, slice) inputs: the slice's token window plus its
            # global start position (the stage fn derives positions and
            # the causal mask against the retained-KV prefix from it).
            micros_sl = {
                (j, s): {**{k: val[:, s * Ls:(s + 1) * Ls]
                            for k, val in micros[j].items()},
                         "offset": jnp.int32(s * Ls)}
                for j in range(m) for s in range(c)}
            # The sliced last stage returns UN-normalized nll sums; the
            # whole-microbatch valid-token count normalizes them so the
            # summed slice losses equal the unchunked stage loss.
            cnt = [jnp.maximum(jnp.sum(
                (micros[j]["labels"] >= 0).astype(jnp.float32)), 1.0)
                for j in range(m)]
            dt = jnp.dtype(cfg.dtype)
            nkv, hd = cfg.num_kv_heads, cfg.head_dim
            kv_zero = [tuple((jnp.zeros((self.b, 0, nkv, hd), dt),
                              jnp.zeros((self.b, 0, nkv, hd), dt))
                             for _ in self.splitter.assign[vs])
                       for vs in range(nv)]
            # (vs, mb, sl) -> pending dKV cotangent: prefix gradients the
            # LATER slices' backwards (which run first — reverse slice
            # order) have emitted for slice sl's own KV.
            dkv_acc: Dict[Tuple[int, int, int], Any] = {}

        def kv_prefix_for(i, vs, mb, chunk, sl):
            """Concatenate earlier slices' retained KV (slice order =
            global position order), reading through ``store.peek`` so
            the prefix is reachable wherever a residency policy moved
            the earlier units (partner store, host, dropped). A
            host-resident slice's KV is copied to the device for the
            read; its stash stays where the policy put it."""
            if sl == 0:
                return kv_zero[vs]
            parts = [mem_offload.to_device(store.peek(i, mb, chunk, j)[-1])
                     for j in range(sl)]
            return tuple(
                (jnp.concatenate([part[li][0] for part in parts], axis=1),
                 jnp.concatenate([part[li][1] for part in parts], axis=1))
                for li in range(len(kv_zero[vs])))

        def wrap(body):
            """Shared post-instruction bookkeeping: the profiler span
            around the handler body (a BLOCKED attempt is host time too),
            span emission through the attached observer (blocking so the
            span covers real device time, not async dispatch) and the
            live stash-cap assertion."""
            def handler(i, ins):
                t0 = time.perf_counter() if observer is not None else 0.0
                with profile_span("pipeline." + ins.op, op=ins.op, stage=i,
                                  mb=ins.mb, chunk=ins.chunk, sl=ins.sl,
                                  phase=ins.phase, step=step_id):
                    sync = body(i, ins)
                if sync is P.BLOCKED:
                    return P.BLOCKED
                if observer is not None:
                    if sync is not None:
                        jax.block_until_ready(sync)
                    observer.emit(
                        ins.op, i, ins.mb, ins.chunk, ins.sl, ins.phase,
                        t0 - t_step0, time.perf_counter() - t_step0,
                        hbm=store.resident_bytes(i))
                if self.enforce_cap and self.cap is not None:
                    # swap ops (EVICT/LOAD) also touch the partner's
                    # store — check both ends so acceptor-side transients
                    # can't hide behind the acceptor's next pop.
                    for dev in ((i, partner[i])
                                if ins.op in swap_ops else (i,)):
                        assert store.held(dev) <= bounds[dev], \
                            (dev, ins, store.held(dev), bounds[dev])
                return None
            return handler

        def on_f(i, ins):
            vs = ins.vs
            # pop: the boundary activation has exactly one consumer;
            # holding it past this F would overhang the stash accounting
            # the cap is asserted on.
            carry = dummy if vs == 0 else act_in.pop((vs, ins.mb, ins.sl),
                                                     None)
            if carry is None:
                return P.BLOCKED
            if not sliced:
                out, vjp_fn = jax.vjp(
                    self.stage_fns[vs], stage_params[vs], carry,
                    micros[ins.mb])
                # recompute residency keeps the boundary input alongside
                # the residuals: DROP strips to it, RECOMPUTE re-forwards
                # from it
                store.put(i, ins.mb,
                          (vjp_fn, carry) if is_recompute else vjp_fn,
                          ins.chunk)
                if vs == nv - 1:
                    losses[(ins.mb, 0)] = out
                else:
                    act_in[(vs + 1, ins.mb, 0)] = out
                return out
            sl = ins.sl
            kv_prefix = kv_prefix_for(i, vs, ins.mb, ins.chunk, sl)
            (primary, kv_own), vjp_fn = jax.vjp(
                self.stage_fns[vs], stage_params[vs], carry, kv_prefix,
                micros_sl[(ins.mb, sl)])
            # the slice's own KV rides in the stash entry (last element)
            # so later slices' forwards — and the residency machinery —
            # see ONE unit, not a separate KV cache
            store.put(i, ins.mb,
                      (vjp_fn, carry, kv_own) if is_recompute
                      else (vjp_fn, kv_own), ins.chunk, sl)
            if vs == nv - 1:
                nll_sum, aux = primary
                losses[(ins.mb, sl)] = nll_sum / cnt[ins.mb] + aux
            else:
                act_in[(vs + 1, ins.mb, sl)] = primary
            return primary

        def on_b(i, ins):
            nonlocal accum_calls
            vs = ins.vs
            if vs == nv - 1:
                cot = (scale / cnt[ins.mb], scale) if sliced else scale
            else:
                cot = grad_in.pop((vs, ins.mb, ins.sl), None)
                if cot is None:
                    return P.BLOCKED
            entry = store.pop(i, ins.mb, ins.chunk, ins.sl)
            if not sliced:
                vjp_fn = entry[0] if is_recompute else entry
                d_sp, d_carry, _ = vjp_fn(cot)
            else:
                sl = ins.sl
                vjp_fn, kv_own = entry[0], entry[-1]
                # dKV for this slice's own KV: what LATER slices'
                # backwards (already run — reverse slice order) emitted
                cot_kv = dkv_acc.pop((vs, ins.mb, sl), None)
                if cot_kv is None:       # newest slice: nothing pending
                    cot_kv = jax.tree.map(jnp.zeros_like, kv_own)
                d_sp, d_carry, d_kvp, _ = vjp_fn((cot, cot_kv))
                for j in range(sl):      # scatter prefix grads backward
                    seg = tuple((dk[:, j * Ls:(j + 1) * Ls],
                                 dv[:, j * Ls:(j + 1) * Ls])
                                for dk, dv in d_kvp)
                    prev = dkv_acc.get((vs, ins.mb, j))
                    dkv_acc[(vs, ins.mb, j)] = seg if prev is None \
                        else jax.tree.map(jnp.add, prev, seg)
            with profile_span("pipeline.grad_accum", stage=i, step=step_id):
                if grads[vs] is None:
                    grads[vs] = d_sp
                else:
                    # donates only the running sum: d_sp is returned below
                    # and an observed step blocks on it
                    grads[vs] = self.accumulate(grads[vs], d_sp)
                    accum_calls += 1
            if vs > 0:
                grad_in[(vs - 1, ins.mb, ins.sl)] = d_carry
            return (d_sp, d_carry)

        # Every move handler follows the compiled ISSUE/WAIT contract:
        # the ISSUE half starts the (async) copy and registers it with
        # the transfer runtime; the WAIT half blocks on the channel up to
        # that unit, so the dependent compute touches the data only once
        # the copy is really complete — and the depth cap is enforced at
        # submit time.
        def on_evict(i, ins):
            if ins.is_wait:
                return xfers.wait(chan(ins.op, i), ins.done_key)
            return xfers.submit(
                chan(ins.op, i), ins.done_key,
                lambda: store.evict(i, ins.mb, partner[i], ins.chunk,
                                    ins.sl))

        def on_load(i, ins):
            if ins.is_wait:
                return xfers.wait(chan(ins.op, i), ins.done_key)
            return xfers.submit(
                chan(ins.op, i), ins.done_key,
                lambda: store.load(i, ins.mb, partner[i], ins.chunk,
                                   ins.sl))

        def on_offload(i, ins):
            if ins.is_wait:
                return xfers.wait(chan(ins.op, i), ins.done_key)
            # real D2H: the vjp closure is a tree_util.Partial pytree, so
            # device_put moves the residual arrays to pinned host memory
            return xfers.submit(
                chan(ins.op, i), ins.done_key,
                lambda: store.offload(i, ins.mb, ins.chunk, ins.sl,
                                      mover=mem_offload.to_host))

        def on_fetch(i, ins):
            if ins.is_wait:
                return xfers.wait(chan(ins.op, i), ins.done_key)
            return xfers.submit(
                chan(ins.op, i), ins.done_key,
                lambda: store.fetch(i, ins.mb, ins.chunk, ins.sl,
                                    mover=mem_offload.to_device))

        def on_drop(i, ins):
            if ins.is_wait:
                return None
            # free the residuals (the vjp closure reference), keep the
            # boundary input the re-forward starts from — plus, under
            # slicing, the slice's own KV (later slices peek at it)
            strip = (lambda e: (e[1], e[2])) if sliced else (lambda e: e[1])
            store.drop(i, ins.mb, ins.chunk, ins.sl, strip=strip)

        def on_recompute(i, ins):
            if ins.is_wait:
                return None
            vs = ins.vs
            kept = store.dropped_input(i, ins.mb, ins.chunk, ins.sl)
            if not sliced:
                carry = kept
                out, vjp_fn = jax.vjp(
                    self.stage_fns[vs], stage_params[vs], carry,
                    micros[ins.mb])
                store.recompute(i, ins.mb, (vjp_fn, carry), ins.chunk)
                return out
            carry = kept[0]
            kv_prefix = kv_prefix_for(i, vs, ins.mb, ins.chunk, ins.sl)
            (primary, kv_own), vjp_fn = jax.vjp(
                self.stage_fns[vs], stage_params[vs], carry, kv_prefix,
                micros_sl[(ins.mb, ins.sl)])
            store.recompute(i, ins.mb, (vjp_fn, carry, kv_own), ins.chunk,
                            ins.sl)
            return primary

        # Handlers by registered policy mechanism (like the simulator's
        # pricing set): a plugin policy's ops are executable without
        # edits here — the registry IS the op set.
        mech_release = {"swap": on_evict, "host": on_offload,
                        "recompute": on_drop}
        mech_restore = {"swap": on_load, "host": on_fetch,
                        "recompute": on_recompute}
        handlers = {F: wrap(on_f), B: wrap(on_b)}
        for op, pol in respol.RELEASE_OPS.items():
            handlers[op] = wrap(mech_release[pol.mechanism])
        for op, pol in respol.RESTORE_OPS.items():
            handlers[op] = wrap(mech_restore[pol.mechanism])
        P.run(schedule.streams, handlers, observer=observer, dep_gated=True)
        xfers.drain()                       # no copy escapes the step

        with profile_span("pipeline.merge", step=step_id):
            loss = sum(losses.values()) * scale
            full_grads = self.splitter.merge(grads)
        stats = store.stats()
        stats.transfers_inflight_peak = xfers.inflight_peak
        stats.grad_accum_calls = accum_calls
        return StepResult(loss=loss, grads=full_grads, stats=stats,
                          events=list(recorder.spans)
                          if recorder is not None else None)
