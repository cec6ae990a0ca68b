"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists: the device operations of each chip (start, end, name, category, in
nanoseconds on the profiler's clock) and the benchmark's own host spans
(``jax.profiler.TraceAnnotation`` named ``bench.*``), on the same clock.
``reduce`` turns those lists into busy time, each replay leg's device time,
the idle share inside the replay spans, and the breakdown.  Both halves are
plain functions of their input so that a small recorded trace can check them.
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

#: the lines of a device plane: one event per executed HLO op (its name is
#: the op's HLO text), and one per executed program
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "collective-permute",
               "reduce-scatter")
#: ops whose event spans the ops of their body: counted in busy time, never
#: in a leg, so no body is counted twice
CONTAINERS = ("while", "conditional", "call")


@dataclass
class Trace:
    #: device plane name -> [(start_ns, end_ns, op)], ``op`` an index into
    #: ``names``
    ops: Dict[str, List[Tuple[float, float, int]]] = \
        field(default_factory=dict)
    #: the HLO text of every distinct op
    names: List[str] = field(default_factory=list)
    #: [(start_ns, end_ns, name)] of the benchmark's host spans
    spans: List[Tuple[float, float, str]] = field(default_factory=list)
    #: device plane name -> [(start_ns, end_ns, program name)]
    modules: Dict[str, List[Tuple[float, float, str]]] = \
        field(default_factory=dict)


def latest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, device_prefix: str = "/device:TPU:") -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    tr = Trace()
    index: Dict[str, int] = {}
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            ops = tr.ops.setdefault(plane.name, [])
            mods = tr.modules.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        i = index.setdefault(e.name, len(index))
                        ops.append((e.start_ns, e.end_ns, i))
                elif line.name == MODULES_LINE:
                    mods.extend((e.start_ns, e.end_ns, e.name.split("(")[0])
                                for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        tr.spans.append((e.start_ns, e.end_ns, e.name))
    tr.names = sorted(index, key=index.get)
    tr.spans.sort()
    return tr


def parse_op(text: str) -> Tuple[str, str, str]:
    """(name, opcode, fusion kind) from an op's HLO text, e.g.
    ``%fusion.3 = f32[8]{0} fusion(...), kind=kOutput, calls=...`` gives
    ("fusion.3", "fusion", "kOutput")."""
    head, _, rest = text.partition(" = ")
    name = head.strip().lstrip("%")
    i = 0
    if rest.startswith("("):                  # a tuple shape
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        i += 1
    j = rest.find(" ", i)
    k = rest.find("(", j + 1)
    opcode = rest[j + 1:k] if j >= 0 and k > j else ""
    kind = ""
    at = rest.rfind("kind=")
    if at >= 0:
        kind = rest[at + 5:].split(",")[0].split()[0]
    return name, opcode, kind


def leg(text: str) -> Optional[str]:
    """Which replay leg an op belongs to by its own HLO: matmuls (a
    convolution or dot, or an output fusion, which on the TPU is built
    around one) to the compute leg, collectives to the collective leg,
    loop and call containers to none, everything else to the memory leg.
    ``leg_times`` then counts a loop to the leg of its body."""
    name, opcode, kind = parse_op(text)
    if opcode in CONTAINERS:
        return None
    if any(opcode.startswith(c) or name.startswith(c) for c in COLLECTIVES):
        return "collective"
    if opcode in ("convolution", "dot") or kind == "kOutput" \
            or name.startswith("convolution"):
        return "compute"
    return "memory"


def leg_times(ops, legs_of) -> Dict[str, float]:
    """Device time of each leg over ``ops`` ([(start, end, op)],
    ``legs_of[op]`` the op's own leg).  A loop whose body holds ops and no
    further loop counts whole, overhead between iterations included, to
    the leg of its body: compute where the body holds a matmul, else
    collective where it holds a collective, else memory.  So the compute
    atom's loop, with its carry copy and its loop control, is compute
    time.  Ops outside such a loop count by their own leg; an outer loop
    (the replay's scan over its rows) adds only its own bookkeeping ops."""
    order = sorted(range(len(ops)), key=lambda j: (ops[j][0], -ops[j][1]))
    owner = [-1] * len(ops)
    stack: List[int] = []
    for j in order:
        a, b, i = ops[j]
        # an op belongs to the loops open where it starts: its end may
        # pass its loop's by the trace's rounding
        while stack and ops[stack[-1]][1] <= a:
            stack.pop()
        owner[j] = stack[-1] if stack else -1
        if legs_of[i] is None:
            stack.append(j)
    body: Dict[int, set] = {}
    outer = set()
    for j, (a, b, i) in enumerate(ops):
        if owner[j] < 0:
            continue
        if legs_of[i] is None:
            outer.add(owner[j])
        else:
            body.setdefault(owner[j], set()).add(legs_of[i])
    out: Dict[str, float] = {}

    def add(k, dt):
        out[k] = out.get(k, 0.0) + dt
    for j, (a, b, i) in enumerate(ops):
        if legs_of[i] is None:
            if j in body and j not in outer:
                add(next(k for k in ("compute", "collective", "memory")
                         if k in body[j]), b - a)
        elif owner[j] < 0 or owner[j] in outer:
            add(legs_of[i], b - a)
    return out


def union(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged, lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that the merged intervals cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


class _Intervals:
    """Sorted, disjoint labelled intervals; ``at(t)`` is the label of the
    one holding ``t``, or None."""

    def __init__(self, items):
        self.items = sorted(items)
        self.starts = [a for a, _, _ in self.items]

    def at(self, t):
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0 and t <= self.items[i][1]:
            return self.items[i][2]
        return None


@dataclass
class Reduced:
    chips: int
    window_s: float
    busy_s: float                       # mean over chips
    replay_span_s: float                # summed over replay spans
    replay_busy_s: float                # mean over chips
    n_replays: int
    legs_s: Dict[str, float]            # mean over chips that ran the leg
    breakdown: Dict[str, list]


def reduce(tr: Trace, replay_span: str = "bench.replay",
           top: int = 10) -> Optional[Reduced]:
    """None where the trace holds no device op or no replay span.  The
    window is the stretch from the first host span's start to the last
    one's end; device ops are clipped to it."""
    replays = [sp for sp in tr.spans if sp[2] == replay_span]
    devices = [d for d, ops in sorted(tr.ops.items()) if ops]
    if not replays or not devices:
        return None
    lo = min(a for a, _, _ in tr.spans)
    hi = max(b for _, b, _ in tr.spans)
    in_replay = _Intervals(replays)
    legs_of = [leg(n) for n in tr.names]
    short = [" ".join(x for x in parse_op(n) if x) for n in tr.names]
    span_s = sum(b - a for a, b, _ in replays)
    busy, rbusy, legs, by_op = [], [], {}, {}
    for d in devices:
        module = _Intervals(tr.modules.get(d, []))
        ops = [o for o in tr.ops[d] if o[1] > lo and o[0] < hi]
        merged = union((a, b) for a, b, _ in ops)
        busy.append(covered(merged, lo, hi))
        rbusy.append(sum(covered(merged, a, b) for a, b, _ in replays))
        for a, b, i in ops:
            if legs_of[i] is not None:
                key = f"{module.at(a) or '?'} {short[i]}"
                by_op[key] = by_op.get(key, 0.0) + (b - a)
        for k, v in leg_times(_replay_ops(tr, d, ops, in_replay),
                              legs_of).items():
            legs.setdefault(k, []).append(v)
    n = len(devices)
    ops_top = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return Reduced(
        chips=n, window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy) / n * 1e-9,
        replay_span_s=span_s * 1e-9,
        replay_busy_s=sum(rbusy) / n * 1e-9,
        n_replays=len(replays),
        legs_s={k: sum(v) / len(v) * 1e-9 for k, v in legs.items()},
        breakdown={"device_ops": [[k, v / n * 1e-9] for k, v in ops_top],
                   "idle_gaps": _idle_gaps(tr, devices[0], lo, hi)[:top]})


def _replay_ops(tr: Trace, device: str, ops, in_replay: _Intervals):
    """The ops of the programs whose middle lies in a replay span.  The
    programs' intervals are on the device's clock, so an op is placed by
    the program running it and not by a host span, which may sit a little
    off that clock and then splits a loop from its body.  A device with ops
    and no program line is an error, not a guess."""
    mods = tr.modules.get(device)
    if not mods:
        raise ValueError(f"{device} ran ops but the trace has no "
                         f"{MODULES_LINE!r} line for it")
    within = _Intervals([(a, b, "replay") for a, b, _ in mods
                         if in_replay.at((a + b) / 2) is not None])
    return [o for o in ops if within.at(o[0]) is not None]


def _idle_gaps(tr: Trace, device: str, lo: float, hi: float):
    """The longest stretches of ``[lo, hi]`` in which ``device`` ran
    nothing, each named by the innermost host span open over its middle."""
    merged = union((a, b) for a, b, _ in tr.ops[device])
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    gaps = []
    for a, b in zip(edges[::2], edges[1::2]):
        a, b = max(a, lo), min(b, hi)
        if b > a:
            mid = (a + b) / 2
            open_ = [(e - s, n) for s, e, n in tr.spans if s <= mid <= e]
            gaps.append([min(open_)[1] if open_ else "no span",
                         (b - a) * 1e-9])
    return sorted(gaps, key=lambda g: -g[1])
