"""Where the replay's idle device time goes, read from the program's spans.

The emulator opens ``synapse.*`` spans (``repro.obs.span``, a
``jax.profiler.TraceAnnotation``) around its phases, so a profiler trace
holds them on the host plane beside the benchmark's ``bench.*`` spans and on
the clock the device planes are converted to.  From a ``trace.Trace`` (device
ops, programs and ``bench.*`` spans) and those program spans:

- ``split`` gives each instant of a ``bench.replay`` span in which a device
  ran no op to exactly one cause (``CAUSES``);
- ``skew_ns`` bounds the offset of the device clock from the host clock by
  causality, tighter with the TPU runtime's own enqueue and done events
  (``runtime_marks``);
- ``idle_gaps`` names the longest idle stretches by the innermost span,
  benchmark and program spans together;
- ``reduce`` gathers them, with the split taken at the middle of the skew
  bound (the raw stamps can sit a millisecond off the host's clock), and
  only where the runtime's marks bound it and the bound is sound.

``trace.load`` and ``trace.reduce`` are left as they are: every metric they
feed reads what it read before.  All three are plain functions of their
input, so hand-built and recorded traces check them
(``bench/tests/test_idle.py``).
"""
from __future__ import annotations

import bisect
import dataclasses
from typing import Dict, List, Optional, Tuple

from bench.lib import trace as T

PREFIX = "synapse."
REPLAY_SPAN = T.SPAN_PREFIX + "replay"
APP_SPAN = T.SPAN_PREFIX + "app_step"
#: how many of the longest idle gaps ``idle_gaps`` keeps, as ``trace.reduce``
TOP = 10
#: the causes of idle device time inside a replay: a program is running but
#: no op (``program``), else the innermost open program span decides:
#: ``launch`` for ``*.launch`` and ``*.compile``, ``sync`` for ``*.sync``,
#: and ``host`` for any other or none (schedule, accounting, storage, the
#: emulator's and the benchmark's own code)
CAUSES = ("launch", "sync", "program", "host")

Span = Tuple[float, float, str]
#: host events of the TPU runtime that bound when a program ran: it cannot
#: start before its ``DoEnqueueProgram`` starts, and its
#: ``tpu::System::Execute=>Done`` starts only once it has ended
MARKS = {"enqueue": "DoEnqueueProgram", "done": "tpu::System::Execute=>Done"}


def program_spans(path: str) -> List[Span]:
    """[(start_ns, end_ns, name)] of the ``synapse.*`` host events of an
    ``.xplane.pb``, on the clock of ``trace.load``'s spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = [(e.start_ns, e.end_ns, e.name)
           for plane in data.planes if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith(PREFIX)]
    return sorted(out)


def runtime_marks(path: str) -> Dict[str, List[float]]:
    """The start stamps of ``MARKS``' host events in an ``.xplane.pb``,
    sorted, by key; a runtime that writes none gives empty lists."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    want = {v: k for k, v in MARKS.items()}
    out: Dict[str, List[float]] = {k: [] for k in MARKS}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in want:
                        out[want[e.name]].append(e.start_ns)
    return {k: sorted(v) for k, v in out.items()}


def cause(name: str) -> str:
    """The cause of idle device time under the program span ``name``."""
    if name.endswith((".launch", ".compile")):
        return "launch"
    if name.endswith(".sync"):
        return "sync"
    return "host"


def _free(merged, lo: float, hi: float) -> List[Tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that the merged intervals
    (``trace.union``) leave free: ``trace.covered``'s complement."""
    out, t = [], lo
    i = bisect.bisect_right(merged, lo, key=lambda iv: iv[1])
    while i < len(merged) and merged[i][0] < hi:
        s, e = merged[i]
        if s > t:
            out.append((t, s))
        t = max(t, e)
        i += 1
    if t < hi:
        out.append((t, hi))
    return out


def _within(spans: List[Span], lo: float, hi: float) -> List[Span]:
    return [sp for sp in spans if sp[0] < hi and sp[1] > lo]


def _cause_at(spans: List[Span], t: float) -> str:
    open_ = [(e - s, n) for s, e, n in spans if s <= t <= e]
    return cause(min(open_)[1]) if open_ else "host"


def split(tr: T.Trace, program: List[Span],
          offset_ns: float = 0.0) -> Optional[Dict[str, float]]:
    """Seconds of idle device time inside the replay spans by cause, summed
    over the replays and averaged over the chips; the causes add up to the
    replay spans' length less their busy time (``trace.reduce``'s
    ``replay_span_s - replay_busy_s``, at ``offset_ns`` 0).  None where the
    trace holds no program span, no replay span or no device op.

    ``offset_ns`` is an offset of the device clock to take out, as
    ``skew_ns`` gives it: a device stamp ``t`` is read as host time
    ``t - offset_ns``.  The split at both ends of ``skew_ns``'s bound
    shows how much of it the clocks leave open."""
    replays = [sp for sp in tr.spans if sp[2] == REPLAY_SPAN]
    devices = [d for d, ops in sorted(tr.ops.items()) if ops]
    if not program or not replays or not devices:
        return None
    out = dict.fromkeys(CAUSES, 0.0)
    for d in devices:
        busy = T.union((a - offset_ns, b - offset_ns)
                       for a, b, _ in tr.ops[d])
        mods = T.union((a - offset_ns, b - offset_ns)
                       for a, b, _ in tr.modules.get(d, []))
        for a, b, _ in replays:
            spans = _within(program, a, b)
            for s, e in _free(busy, a, b):
                outside = _free(mods, s, e)
                out["program"] += (e - s) - sum(y - x for x, y in outside)
                for x, y in outside:
                    # cut at every program span's edge, so one span is
                    # innermost over each piece
                    cuts = sorted({x, y} | {t for sp in spans
                                            for t in sp[:2] if x < t < y})
                    for u, v in zip(cuts, cuts[1:]):
                        out[_cause_at(spans, (u + v) / 2)] += v - u
    return {k: v / len(devices) * 1e-9 for k, v in out.items()}


def _bounds(mods, episodes) -> Optional[Tuple[float, float]]:
    """Bounds on the offset from one stretch of host time: ``mods`` are the
    programs it ran, [(start, end)] in the device's order, and ``episodes``
    are its launch-then-sync episodes, [(launch start, sync end)] in host
    order.  Each episode enqueued at least one program, and a device runs
    its programs in the order they were enqueued, so program ``j`` (from 0)
    belongs to an episode between ``j - (n - k)`` and ``j``."""
    n, k = len(mods), len(episodes)
    if k == 0 or n < k:
        return None
    lo = max(e - episodes[min(j, k - 1)][1] for j, (_, e) in enumerate(mods))
    hi = min(s - episodes[max(0, j - n + k)][0]
             for j, (s, _) in enumerate(mods))
    return lo, hi


def _episodes(spans: List[Span], start: float):
    """[(launch start, sync end)] of the program spans in one replay: each
    ``*.sync`` span with the earliest ``*.launch`` span that starts between
    the previous sync's end (or ``start``) and its own start."""
    launches = sorted(s for s, _, n in spans if n.endswith(".launch"))
    out, prev = [], start
    for s, e, _ in sorted(sp for sp in spans if sp[2].endswith(".sync")):
        first = [t for t in launches if prev <= t <= s]
        if not first:
            return None
        out.append((first[0], e))
        prev = e
    return out


def _tighten(episodes, marks):
    """Each episode's launch start moved up to the first enqueue mark in
    it, and its sync end back to the last done mark in it, where it has
    them: its programs were enqueued no earlier, and done no later."""
    enq, done = marks.get("enqueue", []), marks.get("done", [])
    out = []
    for a, b in episodes:
        i = bisect.bisect_left(enq, a)
        j = bisect.bisect_right(done, b)
        out.append((enq[i] if i < len(enq) and enq[i] <= b else a,
                    done[j - 1] if j > 0 and done[j - 1] >= a else b))
    return out


def skew_ns(tr: T.Trace, program: List[Span],
            marks: Optional[Dict[str, List[float]]] = None
            ) -> Optional[Tuple[float, float]]:
    """(lo, hi): bounds in nanoseconds on the offset of the device clock
    from the host clock, the ``d`` for which a device stamp ``t`` happened
    at host time ``t - d``, from causality alone: a program cannot start
    before the start of the launch span that enqueued it, and a sync span
    cannot end before its program ends.  Each replay's episodes come from
    its program spans; an application step's span is one episode of its
    own.  With the runtime's ``marks`` (``runtime_marks``) an episode's
    enqueue and done marks stand in for its launch start and sync end.
    A program belongs to the span that holds its middle.  Over several
    chips the bounds are the widest of theirs.  None where no stretch
    bounds it; ``lo > hi`` would mean the spans break causality."""
    found = []
    for d, mods in sorted(tr.modules.items()):
        mods = sorted((a, b) for a, b, _ in mods)
        mids = [(a + b) / 2 for a, b in mods]
        lo, hi = [], []
        for a, b, name in tr.spans:
            if name == APP_SPAN:
                episodes = [(a, b)]
            elif name == REPLAY_SPAN:
                episodes = _episodes(_within(program, a, b), a)
            else:
                continue
            if episodes and marks:
                episodes = _tighten(episodes, marks)
            inside = mods[bisect.bisect_left(mids, a):
                          bisect.bisect_right(mids, b)]
            got = _bounds(inside, episodes or [])
            if got is not None:
                lo.append(got[0])
                hi.append(got[1])
        if lo:
            found.append((max(lo), min(hi)))
    if not found:
        return None
    return min(f[0] for f in found), max(f[1] for f in found)


def idle_gaps(tr: T.Trace, program: List[Span]):
    """``trace.reduce``'s ``idle_gaps``, with each gap named by the
    innermost span, benchmark and program spans together.  The window is
    still the stretch of the ``bench.*`` spans."""
    devices = [d for d, ops in sorted(tr.ops.items()) if ops]
    if not devices or not tr.spans:
        return []
    lo = min(a for a, _, _ in tr.spans)
    hi = max(b for _, b, _ in tr.spans)
    both = dataclasses.replace(tr, spans=sorted(tr.spans + list(program)))
    return T._idle_gaps(both, devices[0], lo, hi)[:TOP]


def reduce(tr: T.Trace, program: List[Span],
           marks: Optional[Dict[str, List[float]]] = None) -> Optional[dict]:
    """What the replay's idle split reads from one trace: the skew bound,
    whether the runtime's marks made it (``bounded_by_marks``), the split
    at the middle of that bound (``offset_ns``), the split at the raw
    stamps, whose causes add up to ``trace.reduce``'s replay idle, and the
    idle gaps.  None where ``split`` gives None.

    The split and its offset are None unless both kinds of ``MARKS`` are
    there and the bound is sound (``lo <= hi``): from the spans alone the
    bound is about as wide as the launch and sync themselves, so its
    middle says nothing of the split, and an inverted bound means
    programs were placed in the wrong episode."""
    raw = split(tr, program)
    if raw is None:
        return None
    skew = skew_ns(tr, program, marks)
    bounded = (bool(marks) and all(marks.get(k) for k in MARKS)
               and skew is not None and skew[0] <= skew[1])
    offset = (skew[0] + skew[1]) / 2 if bounded else None
    return {"skew_ns": skew, "bounded_by_marks": bounded,
            "offset_ns": offset,
            "replay_idle_s": split(tr, program, offset) if bounded else None,
            "raw_replay_idle_s": raw,
            "idle_gaps": idle_gaps(tr, program)}
