"""The collective atom's readers on hand-built trace reductions: nothing
without a collective leg, the hand-computed numbers with one, and exactly
100% of the interconnect peak when the leg moves its wire at that peak."""
import os
from types import SimpleNamespace

import pytest

from bench import run as R
from bench.lib import peaks
from bench.lib.trace import Reduced
from repro.core.atoms import CollectiveQuant

PEAK = peaks.PEAKS["TPU v5 lite"]
#: one iteration: an all-reduce of 32768 float32 a chip over 4 chips,
#: 2 x 3/4 x 128 KiB on the wire, ring model
WIRE = 196608


def reader(name):
    return R.load_module(os.path.join(R.BENCH, "metrics", name + ".py"))


def reduced(legs, n_replays=2):
    return Reduced(chips=4, window_s=1.0, busy_s=0.9, replay_span_s=0.6,
                   replay_busy_s=0.5, n_replays=n_replays, legs_s=legs,
                   breakdown={})


def run_of(legs, iters, trace=True, peak=PEAK):
    return SimpleNamespace(
        chips=4, peak=peak,
        trace=reduced(legs) if trace else None,
        schedule={"compute_iters": 10, "memory_iters": 5,
                  "collective_iters": iters})


def test_one_iteration_moves_what_the_program_says():
    assert CollectiveQuant(n=4).wire_bytes_per_iter == WIRE


@pytest.mark.parametrize("name", ["collective_atom_ms",
                                  "collective_atom_roofline"])
@pytest.mark.parametrize("run", [
    run_of({"compute": 0.4, "memory": 0.1}, 0),        # one chip's replay
    run_of({"compute": 0.4, "collective": 0.0}, 100),  # leg never ran
    run_of({"collective": 0.2}, 100, trace=False),     # untraced run
], ids=["no_leg", "empty_leg", "untraced"])
def test_no_collective_leg_reads_nothing(name, run):
    assert reader(name).read(run) is None


def test_hand_computed_readings():
    # two traced replays, 0.05 s of collective leg on each chip in all
    run = run_of({"compute": 0.4, "collective": 0.05}, 10000)
    assert reader("collective_atom_ms").read(run) == pytest.approx(25.0)
    burned = 10000 * WIRE * 2                      # bytes a chip
    want = 100.0 * burned / 0.05 / 200e9           # 39.3216%
    assert reader("collective_atom_roofline").read(run) == \
        pytest.approx(want)
    assert want == pytest.approx(39.3216)


def test_leg_at_the_peak_reads_one_hundred():
    iters = 50000
    leg_s = iters * WIRE * 2 / PEAK["ici_bytes_per_s"]
    run = run_of({"collective": leg_s}, iters)
    assert reader("collective_atom_roofline").read(run) == \
        pytest.approx(100.0, rel=1e-12)


def test_roofline_needs_a_peak():
    run = run_of({"collective": 0.05}, 10000, peak=None)
    assert reader("collective_atom_roofline").read(run) is None
    assert reader("collective_atom_ms").read(run) == pytest.approx(25.0)
