"""Fused schedule compiler: equivalence with the per-sample path.

Pins the ISSUE 2 contracts:
  * fused and legacy replay consume bit-identical ResourceVector totals,
    including profiles with interleaved storage legs, and execute the same
    number of samples in the same order;
  * the compiler's iteration tables quantize exactly like the atoms
    (respecting the one-iteration minimums: one compute iter = 2*tile^3
    flops, one memory iter = 2*block bytes);
  * a storage-free M-sample profile costs O(1) device dispatches fused vs
    O(M x atoms) per-sample;
  * PlanCache builds different keys concurrently (per-key build locks)
    with exact stats; StorageAtom pre-creates the read scratch file at
    plan time; emulate_many caps its pool at len(profiles);
  * the memory leg walks a ring of blocks, one block a pass, on both
    paths, and runs exactly the passes the schedule's table counts;
  * the compute leg burns a group of tiles a loop trip and the rest one
    tile a trip, on both paths, exactly the iterations the table counts;
  * the schedule counts the collective iterations that run in full
    groups of blocks (the trips themselves run on a mesh, in
    ``test_collectives_fused``).
"""
import os
import threading
import time

import numpy as np
import pytest

from repro.core import (BarrierStep, Emulator, FusedSegment, Plan, PlanCache,
                        ResourceVector, Sample, StorageAtom, SynapseProfile,
                        compile_schedule)
from repro.core.atoms import (COLL_GROUP, COMPUTE_GROUP, ComputeAtom,
                              compute_burn_body, compute_operand,
                              ring_windows)
from repro.core.schedule import CompiledSchedule, SegmentRunner
from repro.core.emulator import _collapse

# Small tile/block keep device work tiny while staying above the atoms'
# one-iteration minimums (tile 64 = 524288 flops/iter, block 256 KiB =
# 524288 bytes/iter); the default-size minimums are far larger (33.5 MFLOP
# / 33.5 MB per iteration).
TILE = 64
BLOCK = 1 << 18
FPI = 2.0 * TILE ** 3
BPI = 2.0 * BLOCK


def _em(**kw):
    return Emulator(compute_tile=TILE, mem_block=BLOCK, **kw)


def _rv(flops=0.0, hbm=0.0, sw=0.0, sr=0.0, ici=0.0):
    return ResourceVector(flops=flops, hbm_bytes=hbm,
                          storage_write_bytes=sw, storage_read_bytes=sr,
                          ici_bytes={"all-reduce": ici} if ici else {})


def _profile(rvs, command="sched-test"):
    return SynapseProfile(command=command,
                          samples=[Sample(index=i, resources=r)
                                   for i, r in enumerate(rvs)])


def _alternating(n):
    """Distinct consecutive samples: _collapse cannot merge any of them."""
    return _profile([_rv(flops=(1 + i % 2) * FPI, hbm=(1 + i % 2) * BPI)
                     for i in range(n)])


# ---------------------------------------------------------------------------
# fused vs per-sample equivalence
# ---------------------------------------------------------------------------

def test_fused_matches_legacy_storage_free():
    em = _em(plan_cache=PlanCache())
    prof = _alternating(32)
    legacy = em.emulate(prof, fused=False)
    fused = em.emulate(prof, fused=True)
    assert legacy.mode == "per_sample" and fused.mode == "fused"
    # bit-identical consumed totals (dataclass equality: every field)
    assert fused.consumed == legacy.consumed
    assert fused.consumed == prof.totals
    assert fused.n_samples == legacy.n_samples == 32
    # O(1) dispatches fused vs O(M x atoms) per-sample
    assert fused.n_dispatches == 1
    assert legacy.n_dispatches == 32 * 2


def test_fused_matches_legacy_with_interleaved_storage(tmp_path):
    # compute/memory segments split around checkpoint-style storage legs:
    # [work x3] [write+read burst] [work x2] [read] [work]
    work = _rv(flops=2 * FPI, hbm=BPI)
    rvs = [work, work, _rv(flops=FPI, hbm=2 * BPI),
           _rv(flops=FPI, sw=2 << 20, sr=1 << 20),
           work, _rv(flops=3 * FPI),
           _rv(sr=1 << 20),
           _rv(hbm=2 * BPI)]
    prof = _profile(rvs)
    em = _em()
    em.storage.dir = str(tmp_path)
    try:
        legacy = em.emulate(prof, fused=False)
        fused = em.emulate(prof, fused=True)
    finally:
        em.storage.cleanup()
    assert fused.consumed == legacy.consumed
    assert fused.consumed.storage_write_bytes == 2 << 20
    assert fused.consumed.storage_read_bytes == 2 << 20
    # the two identical leading samples collapse to one execution on both
    # paths, so 8 profile samples replay as 7
    assert fused.n_samples == legacy.n_samples == len(rvs) - 1
    # schedule shape: segments split exactly at the storage barriers
    sched = em.compile(prof)
    kinds = [type(s) for s in sched.steps]
    assert kinds == [FusedSegment, BarrierStep, FusedSegment, BarrierStep,
                     FusedSegment]
    assert fused.n_dispatches < legacy.n_dispatches


def test_fused_respects_scales_and_speed():
    em = _em(speed=2.0)
    prof = _alternating(8)
    legacy = em.emulate(prof, fused=False, flops_scale=3.0, mem_scale=0.5)
    fused = em.emulate(prof, fused=True, flops_scale=3.0, mem_scale=0.5)
    assert fused.consumed == legacy.consumed
    # the schedule quantizes the scaled amounts like the atoms do
    sched = em.compile(prof, flops_scale=3.0, mem_scale=0.5)
    runs = _collapse(prof.samples)
    want = [(em.compute.iters_for(r.flops * 3.0 / em.speed),
             em.memory.iters_for(r.hbm_bytes * 0.5 / em.speed), 0)
            for r, c in runs]
    got = [tuple(row) for s in sched.segments for row in s.table]
    assert got == want


def test_identical_samples_collapse_to_single_row():
    em = _em()
    prof = _profile([_rv(flops=FPI, hbm=BPI)] * 16)
    sched = em.compile(prof)
    assert len(sched.segments) == 1
    seg = sched.segments[0]
    assert seg.n_rows == 1                      # one count-scaled row
    assert seg.compute_iters == em.compute.iters_for(16 * FPI)
    assert seg.memory_iters == em.memory.iters_for(16 * BPI)
    fused = em.emulate(prof, fused=True)
    legacy = em.emulate(prof, fused=False)
    assert fused.consumed == legacy.consumed
    assert fused.n_samples == legacy.n_samples == 1   # both fuse the run


def test_subminimum_amounts_are_noop_rows_but_counted():
    em = _em()
    # below half an iteration: quantizes to 0 iters on both paths, but the
    # profile amounts are still accounted in consumed
    prof = _profile([_rv(flops=FPI * 0.2, hbm=BPI * 0.2),
                     _rv(flops=FPI)])
    sched = em.compile(prof)
    assert [tuple(r) for r in sched.segments[0].table] == \
        [(0, 0, 0), (1, 0, 0)]
    fused = em.emulate(prof, fused=True)
    legacy = em.emulate(prof, fused=False)
    assert fused.consumed == legacy.consumed == prof.totals
    # an all-noop segment issues no dispatch at all
    tiny = _profile([_rv(flops=FPI * 0.2), _rv(hbm=BPI * 0.2)])
    rep = em.emulate(tiny, fused=True)
    assert rep.n_dispatches == 0
    assert rep.consumed == tiny.totals


def test_empty_profile():
    em = _em()
    rep = em.emulate(_profile([]), fused=True)
    assert rep.n_samples == 0 and rep.n_dispatches == 0
    assert rep.consumed == ResourceVector()


def test_fleet_fused_matches_single(tmp_path):
    profs = [_alternating(12) for _ in range(3)]
    em = _em()
    ref = em.emulate(profs[0], fused=True)
    fleet = em.emulate_many(profs, max_workers=3)
    for rep in fleet.reports:
        assert rep.mode == "fused"
        assert rep.consumed == ref.consumed
    # shared SegmentRunner: one program per padded table length
    assert em._segments.n_programs >= 1


# ---------------------------------------------------------------------------
# satellites
# ---------------------------------------------------------------------------

def test_plan_cache_concurrent_distinct_builds():
    """Per-key build locks: two distinct keys build concurrently (a global
    build lock would serialize them and time this out)."""
    cache = PlanCache()
    in_build = threading.Barrier(2, timeout=10)
    results = {}

    def builder(tag):
        def build():
            in_build.wait()       # both builders must be inside at once
            return Plan(lambda: None, 1.0)
        return build

    def worker(key):
        results[key] = cache.get_or_build((key,), builder(key))

    threads = [threading.Thread(target=worker, args=(k,)) for k in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert not any(t.is_alive() for t in threads), \
        "distinct-key builds serialized (or deadlocked) behind a global lock"
    assert cache.stats() == {"plans_built": 2, "hits": 0, "size": 2}


def test_plan_cache_same_key_builds_once():
    cache = PlanCache()
    started = threading.Event()
    release = threading.Event()
    n_builds = [0]

    def slow_build():
        n_builds[0] += 1
        started.set()
        release.wait(timeout=10)
        return Plan(lambda: None, 2.0)

    got = []
    t1 = threading.Thread(
        target=lambda: got.append(cache.get_or_build(("k",), slow_build)))
    t1.start()
    started.wait(timeout=10)
    t2 = threading.Thread(
        target=lambda: got.append(cache.get_or_build(("k",), slow_build)))
    t2.start()
    time.sleep(0.05)              # t2 is parked waiting on the build
    release.set()
    t1.join(timeout=10)
    t2.join(timeout=10)
    assert n_builds[0] == 1
    assert len(got) == 2 and got[0] is got[1]
    assert cache.stats() == {"plans_built": 1, "hits": 1, "size": 1}


def test_plan_cache_failed_build_recovers():
    cache = PlanCache()

    def bad():
        raise RuntimeError("trace failed")

    with pytest.raises(RuntimeError):
        cache.get_or_build(("k",), bad)
    plan = cache.get_or_build(("k",), lambda: Plan(lambda: None, 3.0))
    assert plan.amount == 3.0
    assert cache.stats() == {"plans_built": 1, "hits": 0, "size": 1}


def test_storage_read_precreates_scratch_file(tmp_path):
    atom = StorageAtom(block_bytes=1 << 20, directory=str(tmp_path))
    try:
        plan = atom.plan_read(3 << 20)
        files = os.listdir(tmp_path)
        assert len(files) == 1, "plan_read must create the file at plan time"
        assert os.path.getsize(os.path.join(tmp_path, files[0])) == 3 << 20
        assert plan() == 3 << 20          # the timed leg is a pure read
    finally:
        atom.cleanup()
    assert os.listdir(tmp_path) == []


def test_emulate_many_caps_workers():
    em = _em()
    profs = [_alternating(4) for _ in range(2)]
    fleet = em.emulate_many(profs, max_workers=8)
    assert fleet.max_workers == 2             # capped at len(profiles)
    assert fleet.n_profiles == 2


# ---------------------------------------------------------------------------
# the memory leg's ring
# ---------------------------------------------------------------------------

#: windows in the CPU backend's ring at BLOCK: more than one, so a pass
#: count above it wraps round
R = ring_windows(BLOCK, "cpu")


def _passes(state):
    """Passes each window of a ring state has had.  A pass scales its
    window by 1 + 2**-23 (1.0000001 in float32), and c passes from 1.0
    give exactly 1 + c * 2**-23 while c < 2**22."""
    ring, window = (np.asarray(a) for a in state)
    w = ring.reshape(R, -1)
    assert (w == w[:, :1]).all()              # a pass moves a whole window
    return np.rint((w[:, 0].astype(np.float64) - 1) * 2 ** 23).astype(int), \
        int(window)


def _rotated(n):
    """Passes per window, and the next window, after n passes from 0."""
    return np.bincount(np.arange(n) % R, minlength=R), n % R


def _ring_profile():
    # runs of more than R passes, distinct so none collapse
    return _profile([_rv(flops=FPI, hbm=(R + 3) * BPI),
                     _rv(hbm=(2 * R + 1) * BPI),
                     _rv(flops=2 * FPI, hbm=5 * BPI)])


@pytest.mark.parametrize("fused", [True, False])
@pytest.mark.parametrize("n", [1, R - 1, R, R + 1, 2 * R + 3])
def test_memory_leg_rotates_through_every_window(n, fused):
    """A replay of ``n`` passes, short of, at and past the ring's R
    windows: each window gets the passes that fall on it, on the fused and
    the per-sample path alike, and the next replay carries on from the
    window the last one stopped at, wrapping round past R."""
    assert R > 1
    em = _em()
    prof = _profile([_rv(flops=FPI, hbm=n * BPI)])
    assert em.compile(prof).describe()["memory_iters"] == n
    em.emulate(prof, fused=fused)
    em.emulate(prof, fused=fused)
    state = em._segments._ring if fused else em.memory._ring
    got, window = _passes(state)
    want, want_window = _rotated(2 * n)
    np.testing.assert_array_equal(got, want)
    assert window == want_window


def test_ring_replays_consume_bit_identical_totals():
    """Over more than R passes a row, fused and per-sample replays report
    bit-identical consumed totals, and each ran exactly the passes its
    schedule counts."""
    fused_em, legacy_em = _em(), _em()
    prof = _ring_profile()
    fused = fused_em.emulate(prof, fused=True)
    legacy = legacy_em.emulate(prof, fused=False)
    assert fused.consumed == legacy.consumed == prof.totals
    n = fused_em.compile(prof).describe()["memory_iters"]
    assert _passes(fused_em._segments._ring)[0].sum() == n
    assert _passes(legacy_em.memory._ring)[0].sum() == n


def test_ring_keeps_the_memory_quantization():
    """The ring changes where a pass reads and writes, not what it is
    charged: two block passes an iteration, rounded per row, so the bench's
    burned-bytes check (within half an iteration a row) holds as before."""
    em = _em()
    assert em.memory.bytes_per_iter() == 2 * BLOCK
    prof = _ring_profile()
    desc = em.compile(prof).describe()
    assert desc["memory_iters"] == sum(
        em.memory.iters_for(s.resources.hbm_bytes) for s in prof.samples)
    burned = abs(desc["memory_iters"] * em.memory.bytes_per_iter()
                 - prof.totals.hbm_bytes) / em.memory.bytes_per_iter()
    assert burned <= 0.5 * desc["n_rows"]
    # amounts off the block grid round to the nearest pass, as before
    assert em.memory.iters_for((R + 0.4) * BPI) == R
    assert em.memory.iters_for((R + 0.6) * BPI) == R + 1


def test_memory_block_must_be_whole_lane_rows():
    """A block is a (rows, 128) window of float32: a block size off that
    grid would charge bytes its passes never move."""
    from repro.core.atoms import memory_operand
    ring, window = memory_operand(BLOCK)
    assert ring.shape == (R, BLOCK // 512, 128) and int(window) == 0
    with pytest.raises(ValueError, match="128-lane"):
        memory_operand(BLOCK + 4)


# ---------------------------------------------------------------------------
# the compute leg's grouped loop
# ---------------------------------------------------------------------------

G = COMPUTE_GROUP


def _burned(counts, tile):
    """The compute carry after ``counts[g]`` one-tile iterations of tile g,
    applied tile by tile from ``compute_operand``."""
    tiles = []
    for g, x in enumerate(compute_operand(tile)):
        for _ in range(counts[g]):
            x = compute_burn_body(0, x)
        tiles.append(np.asarray(x))
    return np.stack(tiles)


def _group_counts(n):
    """Iterations each tile of the group takes when a row of ``n`` runs:
    ``n // G`` grouped trips for every tile, then ``n % G`` for the first."""
    return [n // G + (n % G if g == 0 else 0) for g in range(G)]


@pytest.mark.parametrize("n", [0, 1, G - 1, G, G + 1, 3 * G + 2])
def test_compute_leg_burns_exactly_the_planned_tile_iterations(n):
    """The per-sample plan and a fused segment row of ``n`` iterations
    each burn ``n`` tile-iterations: ``n // G`` trips of the whole group,
    then ``n % G`` trips of one tile, with the same arithmetic per tile.
    A 1x1 tile keeps successive iterations apart in float32 (a 256² tile
    saturates to all ones within three), so one iteration too many or too
    few shows."""
    tile = 1
    atom = ComputeAtom(tile=tile)
    plan = atom.plan(n * atom.flops_per_iter())
    assert plan.amount == n * atom.flops_per_iter()
    runner = SegmentRunner(tile=tile)
    seg = runner.launch(FusedSegment(table=np.array([[n, 0, 0]])))
    want = _burned(_group_counts(n), tile)
    if n == 0:                        # nothing to burn, nothing dispatched
        assert plan.launch() is None and seg is None
        return
    short = _burned(_group_counts(n - 1), tile)
    assert np.abs(want - short).max() > 1e-5
    for got in (np.asarray(plan.launch()), np.asarray(seg[0])):
        assert got.shape == (G, tile, tile)
        np.testing.assert_allclose(got, want, rtol=1e-6)


def _plain_burn(tile, iters):
    """The group carry after ``iters`` tile-iterations, from a plain numpy
    float32 loop of the burn's arithmetic on each tile of the group."""
    out = []
    for n in _group_counts(iters):
        c = np.eye(tile, dtype=np.float32) * np.float32(0.5)
        for _ in range(n):
            c = np.tanh(c @ c) * np.float32(0.5) + np.float32(0.5)
        out.append(c)
    return np.stack(out)


@pytest.mark.parametrize("iters", [1, 3, G, G + 1, 2 * G + 1])
@pytest.mark.parametrize("tile", [8, 64])
def test_compute_burn_matches_plain_reference(tile, iters):
    """The atom's plan and a fused row of ``iters`` iterations leave each
    tile of the group where a plain numpy float32 loop of
    ``tanh(c @ c) * 0.5 + 0.5`` from ``eye * 0.5`` leaves it."""
    want = _plain_burn(tile, iters)
    assert want.dtype == np.float32
    atom = ComputeAtom(tile=tile)
    plan = atom.plan(iters * atom.flops_per_iter())
    row = SegmentRunner(tile=tile).launch(
        FusedSegment(table=np.array([[iters, 0, 0]])))
    for got in (np.asarray(plan.launch()), np.asarray(row[0])):
        assert got.shape == (G, tile, tile)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_describe_counts_the_iterations_of_full_group_trips():
    """``compute_grouped_iters`` counts, row by row, the iterations burned
    in full ``G``-tile trips; a row's remainder, and barrier steps, do not
    count.  ``compute_iters`` is unchanged by the grouping."""
    sched = CompiledSchedule(steps=[
        FusedSegment(table=np.array([[G + 1, 0, 0], [G - 1, 3, 0],
                                     [2 * G, 0, 0]])),
        BarrierStep(resources=_rv(flops=5 * G * FPI, sw=1 << 20)),
        FusedSegment(table=np.array([[3 * G + 2, 1, 0], [0, 5, 0]]))])
    desc = sched.describe()
    assert desc["compute_iters"] == (G + 1) + (G - 1) + 2 * G + 3 * G + 2
    assert desc["compute_grouped_iters"] == G + 0 + 2 * G + 3 * G
    assert [s.compute_grouped_iters for s in sched.segments] == [3 * G, 3 * G]
    empty = CompiledSchedule(
        steps=[FusedSegment(table=np.array([[G - 1, 2, 0]]))])
    assert empty.describe()["compute_grouped_iters"] == 0


def test_describe_counts_the_block_iterations_of_full_collective_groups():
    """``collective_grouped_iters`` counts, row by row, the iterations
    burned in full ``COLL_GROUP``-block trips: none of a row under one
    group, all of a row of one group, ``q`` groups of a row of ``q``
    groups and ``r`` blocks.  ``collective_iters`` is unchanged by the
    grouping, and a schedule with no wire reads 0."""
    C = COLL_GROUP
    sched = CompiledSchedule(steps=[
        FusedSegment(table=np.array([[1, 0, C - 1], [0, 2, C],
                                     [3, 0, 3 * C + 5]])),
        BarrierStep(resources=_rv(ici=4e6, sw=1 << 20)),
        FusedSegment(table=np.array([[0, 0, 2 * C + 1], [4, 0, 0]]))])
    desc = sched.describe()
    assert desc["collective_iters"] == (C - 1) + C + (3 * C + 5) + 2 * C + 1
    assert desc["collective_grouped_iters"] == 0 + C + 3 * C + 2 * C
    assert [s.collective_grouped_iters for s in sched.segments] == \
        [4 * C, 2 * C]
    short = CompiledSchedule(
        steps=[FusedSegment(table=np.array([[G, 2, C - 1]]))])
    assert short.describe()["collective_grouped_iters"] == 0
    no_wire = _em().compile(_profile([_rv(flops=3 * FPI, hbm=BPI),
                                      _rv(flops=FPI, ici=4e6)]))
    assert no_wire.describe()["collective_iters"] == 0
    assert no_wire.describe()["collective_grouped_iters"] == 0
