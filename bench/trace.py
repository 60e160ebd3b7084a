"""Reduce a JAX profiler trace (``*.xplane.pb``) to what the per-layer
metrics read.

On a TPU each chip is a plane ``/device:TPU:<n>``. Its line ``XLA Ops``
holds one event per operation the TensorCore ran, and its line
``XLA Modules`` one event per executable run (``jit_<name>(<fingerprint>)``).
The host plane ``/host:CPU`` holds the ``TraceAnnotation`` spans the
benchmark places, on its line ``python``. All times are nanoseconds on one clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import bisect
import re
from typing import Dict, Iterable, List, Tuple

Interval = Tuple[float, float]
#: Ops whose event spans the ops of the computations they run (a
#: ``lax.scan`` is a ``while``): not work of their own, so not busy time.
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r"\)?\s([a-z][a-z0-9-]*)\(")


@dataclasses.dataclass
class Event:
    start: float
    end: float
    name: str


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Event]]        # device id -> XLA Ops
    modules: Dict[int, List[Event]]    # device id -> XLA Modules
    host: List[Event]                  # the benchmark's host spans

    def spans(self, name: str) -> List[Event]:
        return [e for e in self.host if e.name == name]


def load(log_dir: str, host_names: Iterable[str]) -> Trace:
    """Read the one ``*.xplane.pb`` under ``log_dir``, keeping device
    events and the host spans named in ``host_names``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane.pb under {log_dir}, "
                           f"found {paths}")
    return from_profile(ProfileData.from_file(paths[0]), host_names)


def from_profile(pd, host_names: Iterable[str]) -> Trace:
    keep = set(host_names)
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dest is not None:
                    dest[dev] = [Event(e.start_ns, e.start_ns + e.duration_ns,
                                       e.name) for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [Event(e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in line.events if e.name in keep]
    host.sort(key=lambda e: e.start)
    return Trace(ops, modules, host)


def union(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    """Disjoint sorted union of the intervals, clipped to [lo, hi]."""
    out: List[Interval] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(xs: List[Interval], ys: List[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted ``xs`` that no interval of the
    disjoint sorted ``ys`` covers."""
    out, j = [], 0
    for a, b in xs:
        while j < len(ys) and ys[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(ys) and ys[k][0] < b:
            if ys[k][0] > cur:
                out.append((cur, ys[k][0]))
            cur = max(cur, ys[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def work(trace: Trace, dev: int) -> List[Event]:
    """The chip's ``XLA Ops`` events less the container ops."""
    return [e for e in trace.ops.get(dev, []) if opcode(e.name) not in CONTAINERS]


def busy(trace: Trace, dev: int, lo: float, hi: float) -> List[Interval]:
    """Intervals in which an operation ran on the chip's TensorCore."""
    return union(((e.start, e.end) for e in work(trace, dev)), lo, hi)


def opcode(name: str) -> str:
    """The HLO opcode of an ``XLA Ops`` event (``%fusion.3 = bf16[..]
    fusion(...)`` -> ``fusion``); the name itself when it is no HLO
    text."""
    head = name.split(" = ", 1)
    if len(head) < 2:
        return name
    m = _OPCODE.search(head[1])
    return m.group(1) if m else head[1].split("(")[0].split()[-1]


def module_name(name: str) -> str:
    """``jit_fn(1234)`` -> ``jit_fn``."""
    return re.sub(r"\(\d+\)$", "", name)


def module_time(trace: Trace, dev: int, lo: float, hi: float,
                pred) -> float:
    """Device seconds of the executables whose name satisfies ``pred``,
    clipped to [lo, hi]."""
    return length(union(((e.start, e.end) for e in trace.modules.get(dev, [])
                         if pred(module_name(e.name))), lo, hi)) / 1e9


def op_time(trace: Trace, dev: int, lo: float, hi: float, pred) -> float:
    """Device seconds of the operations whose name satisfies ``pred``."""
    return length(union(((e.start, e.end) for e in trace.ops.get(dev, [])
                         if pred(e.name)), lo, hi)) / 1e9


def top_ops(trace: Trace, devs, lo, hi, n=10) -> List[list]:
    """Device seconds per (executable, opcode), summed over chips and
    divided by their number: the most costly first."""
    tot: Dict[str, float] = {}
    for dev in devs:
        mods = sorted(trace.modules.get(dev, []), key=lambda e: e.start)
        starts = [e.start for e in mods]
        for e in work(trace, dev):
            if e.end <= lo or e.start >= hi:
                continue
            i = bisect.bisect_right(starts, e.start) - 1
            mod = module_name(mods[i].name) if i >= 0 and mods[i].end >= e.start else "?"
            key = f"{mod}:{opcode(e.name)}"
            tot[key] = tot.get(key, 0.0) + (min(e.end, hi) - max(e.start, lo))
    k = max(len(list(devs)), 1)
    return [[name, t / 1e9 / k] for name, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, dev: int, lo: float, hi: float,
              labels: Iterable[str], n=10) -> List[list]:
    """The longest gaps in which the chip ran nothing, each named by the
    host span it began in (``other`` where none)."""
    gaps = subtract([(lo, hi)], busy(trace, dev, lo, hi))
    spans = [e for e in trace.host if e.name in set(labels)]

    def label(t):
        inner = [e for e in spans if e.start <= t < e.end]
        return min(inner, key=lambda e: e.end - e.start).name if inner else "other"

    gaps.sort(key=lambda g: g[0] - g[1])
    return [[label(a), (b - a) / 1e9] for a, b in gaps[:n]]
