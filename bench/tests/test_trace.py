"""The trace reduction against numbers worked out by hand."""
import os

import pytest

from bench.lib import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))


NAMES = [
    "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kOutput, calls=%c",
    "%while.2 = (s32[], f32[4]{0}) while((s32[], f32[4]{0}) %t), "
    "condition=%c, body=%b",
    "%fusion.3 = f32[4]{0} fusion(f32[4]{0} %x), kind=kOutput, calls=%c",
    "%copy.4 = f32[4]{0} copy(f32[4]{0} %fusion.3)",
    "%fusion.5 = f32[8]{0} fusion(f32[8]{0} %y), kind=kLoop, calls=%c",
    "%all-reduce.6 = f32[8]{0} all-reduce(f32[8]{0} %z), replica_groups={}",
    "%copy.7 = f32[8]{0} copy(f32[8]{0} %w)",
]


def hand_trace():
    """One chip; an app step span [0, 120] and a replay span [140, 450]
    (nanoseconds).  The replay runs a loop [200, 300] whose body is a
    matmul fusion and a copy of its carry, then a memory op overlapped by
    a collective, then a copy outside any loop."""
    return T.Trace(
        ops={"/device:TPU:0": [
            (10, 100, 0),          # app step
            (200, 300, 1),         # loop: counted in no leg
            (200, 260, 2),         # matmul: compute
            (260, 300, 3),         # carry copy inside the loop: compute
            (300, 350, 4),         # memory
            (340, 360, 5),         # collective
            (400, 420, 6),         # copy outside any loop: memory
        ]},
        names=list(NAMES),
        spans=[(0, 120, "bench.app_step"), (140, 450, "bench.replay")],
        modules={"/device:TPU:0": [(10, 100, "jit_app"),
                                   (200, 420, "jit_segment")]})


def test_union_and_cover():
    m = T.union([(5, 7), (0, 2), (1, 3), (7, 9), (10, 10)])
    assert m == [(0, 3), (5, 9)]
    assert T.covered(m, 2, 6) == 2


@pytest.mark.parametrize("text,want", [
    ("%convolution.7 = f32[8]{0} convolution(f32[8]{0} %a, f32[8]{0} %b)",
     "compute"),
    ("%convolution_multiply_fusion.3 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0}"
     " %f), kind=kOutput, calls=%fc", "compute"),
    ("%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %x), to_apply=%add",
     "collective"),
    ("%all-gather-start.3 = (f32[2]{0}, f32[8]{0}) all-gather-start("
     "f32[2]{0} %x), dimensions={0}", "collective"),
    ("%collective-permute-done = f32[8]{0} collective-permute-done("
     "f32[8]{0} %s)", "collective"),
    ("%fusion.9 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%c",
     "memory"),
    ("%copy.1 = f32[8]{0} copy(f32[8]{0} %a)", "memory"),
    (NAMES[1], None),
])
def test_leg(text, want):
    assert T.leg(text) == want


def test_parse_op_tuple_shape():
    assert T.parse_op(NAMES[1]) == ("while.2", "while", "")
    assert T.parse_op(NAMES[0]) == ("fusion.1", "fusion", "kOutput")


def test_a_loop_counts_whole_to_its_body_leg():
    tr = hand_trace()
    ops = tr.ops["/device:TPU:0"][1:]            # the replay's ops
    got = T.leg_times(ops, [T.leg(n) for n in tr.names])
    # the loop [200, 300] counts whole; its body ops do not count again
    assert got == {"compute": 100, "memory": 70, "collective": 20}


def test_a_body_op_ending_past_its_loop_stays_in_it():
    """Ends rounded past the loop's end do not take the loop's later ops
    out of it."""
    ops = [(0, 100, 1), (0, 60, 2), (60, 101, 3), (101, 110, 6)]
    got = T.leg_times(ops, [T.leg(n) for n in NAMES])
    assert got == {"compute": 100, "memory": 9}


def test_loop_overhead_counts_and_outer_loops_do_not():
    names = list(NAMES) + [
        "%while.8 = (s32[]) while((s32[]) %t), condition=%c, body=%b"]
    ops = [(0, 1000, 7),          # outer loop: the scan over rows
           (10, 500, 1),          # compute loop, 490 ns with overhead
           (10, 110, 2), (200, 240, 3),
           (600, 700, 1),         # memory loop (a loop's leg is its body's)
           (600, 650, 4),
           (800, 810, 6)]         # bookkeeping op of the outer loop
    got = T.leg_times(ops, [T.leg(n) for n in names])
    assert got == {"compute": 490, "memory": 100 + 10}


def test_reduce_by_hand():
    r = T.reduce(hand_trace())
    assert r.chips == 1 and r.n_replays == 1
    assert r.window_s == pytest.approx(450e-9)
    # union: [10,100] + [200,360] + [400,420]
    assert r.busy_s == pytest.approx(270e-9)
    assert r.replay_span_s == pytest.approx(310e-9)
    # inside the replay: [200,360] + [400,420]
    assert r.replay_busy_s == pytest.approx(180e-9)
    assert r.legs_s == pytest.approx({"compute": 100e-9, "memory": 70e-9,
                                      "collective": 20e-9})
    gaps = r.breakdown["idle_gaps"]
    assert gaps[0] == ["bench.replay", pytest.approx(100e-9)]   # 100-200
    assert ["bench.app_step", pytest.approx(10e-9)] in gaps     # 0-10
    assert r.breakdown["device_ops"][0] == [
        "jit_app fusion.1 fusion kOutput", pytest.approx(90e-9)]
    assert ["jit_segment copy.4 copy", pytest.approx(40e-9)] \
        in r.breakdown["device_ops"]


def test_replay_ops_follow_the_device_clock():
    """Host spans a little off the device's clock (here 30 ns late, so the
    replay's first loop starts before its span) place the replay's ops by
    the program that ran them."""
    tr = hand_trace()
    tr.spans = [(30, 150, "bench.app_step"), (230, 480, "bench.replay")]
    assert T.reduce(tr).legs_s == pytest.approx(
        {"compute": 100e-9, "memory": 70e-9, "collective": 20e-9})


def test_reduce_needs_ops_and_a_replay():
    tr = hand_trace()
    assert T.reduce(T.Trace(ops={}, names=tr.names, spans=tr.spans)) is None
    tr.spans = tr.spans[:1]
    assert T.reduce(tr) is None


def test_two_chips_average():
    tr = hand_trace()
    tr.ops["/device:TPU:1"] = [(200, 250, 2)]
    tr.modules["/device:TPU:1"] = [(200, 250, "jit_segment")]
    r = T.reduce(tr)
    assert r.chips == 2
    assert r.replay_busy_s == pytest.approx((180e-9 + 50e-9) / 2)
    assert r.legs_s["compute"] == pytest.approx((100e-9 + 50e-9) / 2)


def test_a_device_without_programs_is_an_error():
    """Ops that no program line places are not placed by host span."""
    tr = hand_trace()
    tr.modules = {}
    with pytest.raises(ValueError, match="XLA Modules"):
        T.reduce(tr)


def recorded():
    import gzip
    import json
    with gzip.open(os.path.join(HERE, "data", "decode_pair.json.gz"),
                   "rt") as f:
        d = json.load(f)
    dev = "/device:TPU:0"
    return T.Trace(ops={dev: [tuple(o) for o in d["ops"][dev]]},
                   names=d["names"], spans=[tuple(s) for s in d["spans"]],
                   modules={dev: [tuple(m) for m in d["modules"][dev]]})


def test_recorded_decode_pair():
    """One decode step of Qwen2-1.5B and its replay, traced on a v5e.  The
    replay's legs add up to its program's device time; the compute leg is
    the compute atom's loop and the memory leg the memory atom's."""
    tr = recorded()
    r = T.reduce(tr)
    ops = tr.ops["/device:TPU:0"]
    seg = [b - a for a, b, n in tr.modules["/device:TPU:0"]
           if n == "jit_segment"]
    assert r.n_replays == 1 and len(seg) == 1
    assert sum(r.legs_s.values()) == pytest.approx(seg[0] * 1e-9, rel=1e-3)
    assert r.replay_busy_s == pytest.approx(seg[0] * 1e-9, rel=1e-3)

    def loop(has, lacks):
        """Device time of the loops whose carry holds ``has`` and not
        ``lacks``: the atom loops carry one operand each, the replay's scan
        over its rows carries both."""
        return sum(b - a for a, b, i in ops
                   if T.parse_op(tr.names[i])[1] == "while"
                   and has in tr.names[i].split(" while(")[0]
                   and lacks not in tr.names[i].split(" while(")[0]) * 1e-9
    tile, block = "f32[256,256]", "f32[4194304]"
    # (loops of the table's zero rows run no body and count to no leg:
    # under a microsecond here)
    assert r.legs_s["compute"] == pytest.approx(loop(tile, block), rel=1e-3)
    # the memory leg is the memory atom's loop and the block's copies into
    # and out of the scan
    assert loop(block, tile) <= r.legs_s["memory"] \
        <= loop(block, tile) + 0.1e-3
    # 1 - 4.04 ms of device time over the 5.54 ms replay span
    assert 100 * (1 - r.replay_busy_s / r.replay_span_s) == \
        pytest.approx(27.0, abs=0.5)
