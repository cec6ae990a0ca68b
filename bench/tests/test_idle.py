"""The split of the replay's idle device time, against numbers worked out
by hand, and on recorded decode pairs."""
import gzip
import json
import os

import pytest

from bench.lib import idle as I
from bench.lib import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"
NAMES = ["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput, "
         "calls=%c"]

#: one replay's program spans (nanoseconds): a schedule, a launch that
#: compiles, a sync and the accounting, inside the emulate call
PROGRAM = [
    (145, 590, "synapse.emulate"),
    (150, 170, "synapse.schedule"),
    (175, 200, "synapse.segment.launch"),
    (180, 195, "synapse.segment.compile"),
    (200, 560, "synapse.segment.sync"),
    (565, 580, "synapse.account"),
]


def hand_trace():
    """An app step span [0, 120] and a replay span [140, 600] on one chip.
    The replay's program [210, 500] runs two ops with a 20 ns gap between
    them."""
    return T.Trace(
        ops={DEV: [(10, 100, 0), (210, 300, 0), (320, 500, 0)]},
        names=list(NAMES),
        spans=[(0, 120, "bench.app_step"), (140, 600, "bench.replay")],
        modules={DEV: [(10, 100, "jit_app"), (210, 500, "jit_segment")]})


def test_each_idle_instant_goes_to_one_cause():
    got = I.split(hand_trace(), PROGRAM)
    # before the program, [140, 210]: 5 under no span, 5 + 20 + 5 under
    # emulate and schedule (host); 5 + 15 + 5 under launch and compile
    # (launch); 10 under sync.  Inside it, [300, 320]: a program is
    # running, whatever span is open.  After it, [500, 600]: 60 under
    # sync; 5 + 15 + 10 under emulate and account, 10 under none (host).
    assert got == pytest.approx({"launch": 25e-9, "sync": 70e-9,
                                 "program": 20e-9, "host": 75e-9})


def test_the_causes_add_up_to_the_replay_idle():
    tr = hand_trace()
    r = T.reduce(tr)
    got = I.split(tr, PROGRAM)
    assert sum(got.values()) == pytest.approx(r.replay_span_s
                                              - r.replay_busy_s)


def test_a_module_wins_over_host_spans():
    tr = hand_trace()
    # a launch span over the gap inside the program changes nothing
    got = I.split(tr, PROGRAM + [(290, 330, "synapse.barrier.launch")])
    assert got["program"] == pytest.approx(20e-9)
    assert got["launch"] == pytest.approx(25e-9)


def test_the_innermost_span_decides():
    # a storage join inside a sync: host work, though a sync is open
    got = I.split(hand_trace(),
                  PROGRAM + [(520, 540, "synapse.storage")])
    assert got["sync"] == pytest.approx(50e-9)
    assert got["host"] == pytest.approx(95e-9)


def test_no_program_spans_no_split():
    assert I.split(hand_trace(), []) is None
    assert I.cause("synapse.barrier.sync") == "sync"
    assert I.cause("synapse.segment.compile") == "launch"
    assert I.cause("synapse.storage") == "host"


def test_two_chips_average():
    tr = hand_trace()
    tr.ops["/device:TPU:1"] = [(10, 100, 0), (210, 500, 0)]
    tr.modules["/device:TPU:1"] = [(10, 100, "jit_app"),
                                   (210, 500, "jit_segment")]
    got = I.split(tr, PROGRAM)
    assert got["program"] == pytest.approx(10e-9)     # (20 + 0) / 2
    assert got["launch"] == pytest.approx(25e-9)


def shifted(tr: T.Trace, d: float) -> T.Trace:
    """The same trace with every device stamp ``d`` later: the device
    clock then runs ``d`` ahead of the host's."""
    return T.Trace(
        ops={k: [(a + d, b + d, i) for a, b, i in v]
             for k, v in tr.ops.items()},
        names=tr.names, spans=tr.spans,
        modules={k: [(a + d, b + d, n) for a, b, n in v]
                 for k, v in tr.modules.items()})


def test_skew_contains_a_planted_offset():
    """The app step's program runs [5, 110] of its span [0, 120]; the
    replay's program is enqueued at the end of its launch [175, 200] and
    runs [205, 500]; its sync ends at 505.  Causality then bounds the
    offset to [-5, 5] on the true clocks; planted 40 ns late, to
    [35, 45]."""
    tr = T.Trace(ops={DEV: [(5, 110, 0), (205, 500, 0)]}, names=NAMES,
                 spans=[(0, 120, "bench.app_step"),
                        (140, 600, "bench.replay")],
                 modules={DEV: [(5, 110, "jit_app"),
                                (205, 500, "jit_segment")]})
    program = [(175, 200, "synapse.segment.launch"),
               (200, 505, "synapse.segment.sync")]
    assert I.skew_ns(tr, program) == (-5, 5)
    lo, hi = I.skew_ns(shifted(tr, 40), program)
    assert (lo, hi) == (35, 45) and lo <= 40 <= hi
    lo, hi = I.skew_ns(shifted(tr, -40), program)
    assert (lo, hi) == (-45, -35)


def test_split_takes_out_an_offset():
    tr = hand_trace()
    want = I.split(tr, PROGRAM)
    assert I.split(shifted(tr, 40), PROGRAM, offset_ns=40) == \
        pytest.approx(want)
    # read 30 ns early, the program runs [180, 470]: 20 of the launch's
    # idle and the 10 of the sync's before the program go, 30 more of the
    # sync's come after it; the sum stays
    got = I.split(tr, PROGRAM, offset_ns=30)
    assert got == pytest.approx({"launch": 5e-9, "sync": 90e-9,
                                 "program": 20e-9, "host": 75e-9})


def test_runtime_marks_tighten_the_skew():
    """The app step's program is enqueued at 2 and done at 112, the
    replay's enqueued at 210, after its launch span, and done at 502,
    before its sync span ends: the offset is then bounded to [-2, 3] on
    the true clocks, against [-5, 5] from the spans alone."""
    tr = T.Trace(ops={DEV: [(5, 110, 0), (215, 500, 0)]}, names=NAMES,
                 spans=[(0, 120, "bench.app_step"),
                        (140, 600, "bench.replay")],
                 modules={DEV: [(5, 110, "jit_app"),
                                (215, 500, "jit_segment")]})
    program = [(175, 200, "synapse.segment.launch"),
               (200, 505, "synapse.segment.sync")]
    marks = {"enqueue": [2, 210], "done": [112, 502]}
    assert I.skew_ns(tr, program) == (-5, 5)
    assert I.skew_ns(tr, program, marks) == (-2, 3)
    lo, hi = I.skew_ns(shifted(tr, 40), program, marks)
    assert (lo, hi) == (38, 43)
    # marks outside an episode leave it as its spans make it
    assert I.skew_ns(tr, program, {"enqueue": [700], "done": [-9]}) == \
        (-5, 5)


def test_skew_with_more_programs_than_syncs():
    """A segment, then a barrier whose one launch enqueues two programs:
    the second program may belong to either episode, the third to the
    barrier's."""
    tr = T.Trace(ops={DEV: [(165, 250, 0), (285, 320, 0), (322, 390, 0)]},
                 names=NAMES, spans=[(140, 420, "bench.replay")],
                 modules={DEV: [(165, 250, "a"), (285, 320, "b"),
                                (322, 390, "c")]})
    program = [(150, 160, "synapse.segment.launch"),
               (160, 260, "synapse.segment.sync"),
               (270, 280, "synapse.barrier.launch"),
               (280, 400, "synapse.barrier.sync")]
    # lo: max(250 - 260, 320 - 400, 390 - 400); hi: min(165 - 150,
    # 285 - 150, 322 - 270)
    assert I.skew_ns(tr, program) == (-10, 15)


def test_skew_without_a_stretch_to_bound_it():
    tr = hand_trace()
    tr.spans = [(140, 600, "bench.replay")]
    assert I.skew_ns(tr, []) is None


def test_idle_gaps_name_program_spans():
    gaps = I.idle_gaps(hand_trace(), PROGRAM)
    # [100, 210] spans the app step's end and the schedule; its middle
    # lies in the schedule
    assert gaps[:2] == [["synapse.schedule", pytest.approx(110e-9)],
                        ["synapse.segment.sync", pytest.approx(100e-9)]]
    assert "bench.replay" not in [g[0] for g in gaps]
    # without program spans the gaps are trace.reduce's
    assert I.idle_gaps(hand_trace(), []) == \
        T.reduce(hand_trace()).breakdown["idle_gaps"]


def recorded(name):
    with gzip.open(os.path.join(HERE, "data", name), "rt") as f:
        d = json.load(f)
    tr = T.Trace(ops={DEV: [tuple(o) for o in d["ops"][DEV]]},
                 names=d["names"], spans=[tuple(s) for s in d["spans"]],
                 modules={DEV: [tuple(m) for m in d["modules"][DEV]]})
    return tr, [tuple(s) for s in d.get("program", [])]


def test_recorded_pair_without_program_spans():
    """The decode pair recorded before the program had spans: no split,
    the gaps as before, and the app step alone bounds the skew: its
    program starts 43 us before its span."""
    tr, program = recorded("decode_pair.json.gz")
    assert program == []
    assert I.split(tr, program) is None
    assert I.idle_gaps(tr, program) == T.reduce(tr).breakdown["idle_gaps"]
    lo, hi = I.skew_ns(tr, program)
    assert hi == pytest.approx(-43260)
    assert lo == pytest.approx(24974706 - 26154817)


def test_reduce_takes_the_split_at_the_middle_of_the_bound():
    tr = shifted(hand_trace(), 30)
    program = PROGRAM
    # the app step's program [40, 130] is enqueued at 5 and done at 105,
    # the replay's [240, 530] enqueued at 180 and done at 505: the offset
    # lies in [130 - 105, 40 - 5] and [530 - 505, 240 - 180]
    marks = {"enqueue": [5, 180], "done": [105, 505]}
    got = I.reduce(tr, program, marks)
    assert got["skew_ns"] == (25, 35)
    assert got["bounded_by_marks"] is True
    assert got["offset_ns"] == 30
    assert got["replay_idle_s"] == pytest.approx(I.split(hand_trace(),
                                                         program))
    assert got["raw_replay_idle_s"] == pytest.approx(I.split(tr, program))
    assert got["idle_gaps"] == I.idle_gaps(tr, program)
    assert I.reduce(tr, [], marks) is None


@pytest.mark.parametrize("marks", [
    None,
    {"enqueue": [], "done": []},
    {"enqueue": [5, 180], "done": []},
], ids=["none", "empty", "no_done"])
def test_reduce_gives_no_split_without_the_marks(marks):
    """From the spans alone the bound is as wide as the launch and sync,
    so its middle says nothing of the split between them."""
    tr = shifted(hand_trace(), 30)
    got = I.reduce(tr, PROGRAM, marks)
    assert got["bounded_by_marks"] is False
    assert got["offset_ns"] is None and got["replay_idle_s"] is None
    # the bound is reported as the marks there make it: (10, 40) from the
    # spans alone, (10, 35) with the enqueue marks
    assert got["skew_ns"] == I.skew_ns(tr, PROGRAM, marks)
    # the raw split and the gaps are read all the same
    assert got["raw_replay_idle_s"] == pytest.approx(I.split(tr, PROGRAM))
    assert got["idle_gaps"] == I.idle_gaps(tr, PROGRAM)


def test_reduce_gives_no_split_on_an_inverted_bound():
    """An enqueue mark after the replay's program has started: the
    program is placed in the wrong episode, and the bound inverts."""
    tr = shifted(hand_trace(), 30)
    got = I.reduce(tr, PROGRAM, {"enqueue": [5, 250], "done": [105, 505]})
    lo, hi = got["skew_ns"]
    assert (lo, hi) == (25, -10) and lo > hi
    assert got["bounded_by_marks"] is False
    assert got["offset_ns"] is None and got["replay_idle_s"] is None


def test_recorded_pair_with_program_spans():
    """A decode step and its replay traced on a v5e with the program's
    spans and the runtime's marks.  At the raw stamps the replay's program
    starts 0.8 ms before its own launch span, so all its idle reads as
    sync; the marks bound the offset to under half a millisecond, and over
    all of that bound the program lies inside the sync span: the launch's
    idle is the launch span, the sync's is the sync span less the
    program."""
    tr, program = recorded("decode_pair_spans.json.gz")
    with gzip.open(os.path.join(HERE, "data", "decode_pair_spans.json.gz"),
                   "rt") as f:
        marks = json.load(f)["marks"]
    r = T.reduce(tr)
    raw = I.split(tr, program)
    assert sum(raw.values()) == pytest.approx(r.replay_span_s
                                              - r.replay_busy_s)
    assert raw["sync"] == pytest.approx(sum(raw.values()), rel=1e-3)

    lo, hi = I.skew_ns(tr, program, marks)
    lo0, hi0 = I.skew_ns(tr, program)
    assert lo0 <= lo < hi <= hi0 < 0
    assert hi - lo < 0.5e6
    span = {n: (b - a) * 1e-9 for a, b, n in program}
    seg = [(b - a) * 1e-9 for a, b, n in tr.modules[DEV]
           if n == "jit_segment"][0]
    for off in (lo, hi):
        got = I.split(tr, program, offset_ns=off)
        # (to the trace's nanosecond)
        assert got["launch"] == pytest.approx(
            span["synapse.segment.launch"], abs=2e-9)
        assert got["sync"] == pytest.approx(
            span["synapse.segment.sync"] - seg, abs=2e-9)
        assert got["program"] < 1e-6
    # the existing reduction reads the replay's legs as on the older pair
    old = T.reduce(recorded("decode_pair.json.gz")[0])
    assert r.legs_s == pytest.approx(old.legs_s, rel=0.01)
    gaps = I.idle_gaps(tr, program)
    assert ["synapse.segment.sync", pytest.approx(
        r.replay_span_s - r.replay_busy_s, rel=1e-3)] in gaps
    got = I.reduce(tr, program, marks)
    assert got["bounded_by_marks"] is True
    assert got["skew_ns"] == (lo, hi)
    assert got["replay_idle_s"] == pytest.approx(
        I.split(tr, program, offset_ns=(lo + hi) / 2))
    assert I.reduce(tr, program)["replay_idle_s"] is None
